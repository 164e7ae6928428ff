"""Single-host drivers for the unified round engine.

:class:`FederatedTrainer` drives the backend-agnostic
:class:`~repro.core.engine.program.RoundProgram` on the
:class:`~repro.core.engine.backends.LocalBackend` (clients vectorised
with ``vmap``). Two compiled drivers share one round body:

* the **single-round driver** (``run_round``) — one jitted round per
  call, the interactive / test path;
* the **scanned multi-round driver** — ``lax.scan`` over
  ``rounds_per_call`` rounds with donated state buffers, so steady-state
  training dispatches one fused program per chunk instead of one per
  round (``benchmarks/bench_convergence.py`` measures the per-round
  dispatch amortisation; DESIGN.md §2 documents the driver).

Both drivers trace the round body exactly once; ``num_traces`` counts
body traces and ``run`` raises when any compiled driver retraces.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manifest import check_manifest, run_manifest
from repro.config import FedConfig, TrainConfig
from repro.core.cross_testing import sampled_eval_batches
from repro.core.engine.backends import LocalBackend
from repro.core.engine.program import RoundProgram, round_keys
from repro.core.scoring import ScoreState, init_scores
from repro.data.pipeline import FederatedDataset, sample_client_batches
from repro.utils import tracing


class RoundState(NamedTuple):
    global_params: Any
    scores: ScoreState
    round_idx: jnp.ndarray
    key: jnp.ndarray
    # per-client [N, D] error-feedback buffer of the compressed
    # exchange (DESIGN.md §12); None — an empty pytree node that
    # threads through scan/checkpoint for free — when uncompressed.
    # Defaulted so uncompressed constructions stay source-compatible.
    comp_state: Any = None


@dataclasses.dataclass
class FederatedTrainer:
    model: Any                      # repro.models.Model
    fed: FedConfig
    train: TrainConfig
    agg_impl: str = "auto"
    eval_batch: int = 256
    use_trust: bool = False
    batch_builder: Optional[Callable] = None   # (bx, by) -> model batch
    rounds_per_call: int = 1        # >1 routes run() through lax.scan
    crosstest_impl: Optional[str] = None  # None -> fed.crosstest_impl
    # 0 keeps the legacy fixed eval prefix (first eval_batch test rows,
    # every round); r > 0 draws schedule-keyed per-tester eval batches
    # that resample every r rounds (DESIGN.md §10)
    eval_resample_every: int = 0

    def __post_init__(self):
        # the program resolves every strategy once, pre-trace (the jitted
        # drivers close over it), and builds the one shared eval fn
        self.program = RoundProgram(
            self.model, self.fed, self.train, use_trust=self.use_trust,
            agg_impl=self.agg_impl, batch_builder=self.batch_builder)
        impl = self.crosstest_impl or getattr(self.fed, "crosstest_impl",
                                              "batched")
        self.backend = self._make_backend(impl)
        # strategy handles (public API, also used by tests/benchmarks)
        self.opt = self.program.opt
        self.aggregator = self.program.aggregator
        self.attack = self.program.attack
        self.selector = self.program.selector
        self.coalition = self.program.coalition
        self.num_traces = 0
        self._dispatched = 0     # run_round calls: the step of its span
        self._round_fn = jax.jit(self._round_body)
        # the scanned driver donates the carried RoundState so XLA can
        # reuse the global-model and score buffers across chunks
        self._scan_fn = (jax.jit(self._multi_round, donate_argnums=0)
                         if self.rounds_per_call > 1 else None)
        self._global_eval = jax.jit(self._global_eval_impl)

    def _make_backend(self, impl: str):
        """Backend factory hook — the population tier overrides this."""
        return LocalBackend(self.fed.num_users, impl)

    # ------------------------------------------------------------------ init
    def init(self, key) -> RoundState:
        pk, rk = jax.random.split(key)
        params = self.model.init(pk)
        comp = (self.program.compressor.init_state(self.fed.num_users)
                if self.program.use_compression else None)
        return RoundState(global_params=params,
                          scores=init_scores(self.fed.num_users),
                          round_idx=jnp.zeros((), jnp.int32),
                          key=rk, comp_state=comp)

    # -------------------------------------------------------- durability
    def manifest(self):
        """Resume-compatibility fingerprint for this trainer's run
        (DESIGN.md §9); stored next to checkpoints and checked by
        ``restore_checkpoint``."""
        return run_manifest(self.model.cfg, self.fed, self.train,
                            use_trust=self.use_trust)

    def state_template(self) -> RoundState:
        """Abstract (shape/dtype-only) RoundState — the template
        ``load_pytree`` restores into. Built via ``eval_shape`` so no
        params are materialised and no PRNG key is consumed."""
        abstract_key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        return jax.eval_shape(self.init, abstract_key)

    def state_dict(self, state: RoundState) -> dict:
        """Host-side (numpy) copy of the complete round state — global
        params, ScoreState (incl. tester trust), round_idx, PRNG key."""
        return {k: jax.tree_util.tree_map(np.asarray, v)
                for k, v in state._asdict().items()}

    def load_state(self, state_dict: dict) -> RoundState:
        """Rebuild a device RoundState from ``state_dict``, casting to
        this trainer's template dtypes; refuses shape mismatches."""
        tmpl = self.state_template()

        def cast(t, leaf):
            leaf = jnp.asarray(leaf)
            if tuple(leaf.shape) != tuple(t.shape):
                raise ValueError(
                    f"state leaf shape {leaf.shape} != template "
                    f"{t.shape} — state from a different run?")
            return leaf.astype(t.dtype)

        # comp_state is absent from pre-§12 state dicts; its default
        # (None) is only valid when this trainer runs uncompressed
        loaded = RoundState(**{k: state_dict[k] for k in tmpl._fields
                               if k in state_dict})
        return jax.tree_util.tree_map(cast, tmpl, loaded)

    def save_checkpoint(self, mgr, state: RoundState,
                        step: Optional[int] = None) -> str:
        """Atomically persist ``state`` (at its own round_idx unless
        ``step`` overrides) plus the run manifest."""
        step = int(state.round_idx) if step is None else int(step)
        return mgr.save(step, state, manifest=self.manifest())

    def restore_checkpoint(self, mgr, step: Optional[int] = None):
        """Restore ``(state, step)`` from the newest loadable
        checkpoint, refusing a manifest mismatch (different config or
        architecture) before touching any arrays."""
        saved = mgr.read_manifest()
        if saved is not None:
            check_manifest(saved, self.manifest())
        return mgr.restore_with_step(self.state_template(), step)

    # ------------------------------------------------------------- internals
    def _round_body(self, state: RoundState, data: FederatedDataset):
        self.num_traces += 1        # python side-effect: runs per trace only
        fed = self.fed
        with jax.named_scope(tracing.SELECT):
            keys = round_keys(jax.random.fold_in(state.key,
                                                 state.round_idx))
            tester_ids, part_mask = self.program.select_round(
                keys, state.round_idx, scores=state.scores.scores)
        with jax.named_scope(tracing.TRAIN):
            bx, by = sample_client_batches(keys.batch, data.train,
                                           fed.local_steps,
                                           self.train.batch_size)
        with jax.named_scope(tracing.CROSS_TEST):
            if self.eval_resample_every > 0:
                # schedule-keyed eval batches: a pure function of the
                # carried run key and the round bucket, derived in-trace —
                # nothing is stashed, so resume stays bit-identical
                # (DESIGN.md §10)
                tx, ty = sampled_eval_batches(
                    state.key, data.test, self.eval_batch, state.round_idx,
                    self.eval_resample_every)
            else:
                tx = data.test.xs[:, :self.eval_batch]
                ty = data.test.ys[:, :self.eval_batch]
        with jax.named_scope(tracing.SCORE):
            server_data = (data.server_x[:self.eval_batch],
                           data.server_y[:self.eval_batch])
        new_global, new_scores, new_comp, metrics = self.program.run(
            self.backend, state.global_params, state.scores,
            bx=bx, by=by, tx=tx, ty=ty,
            tester_ids=tester_ids, part_mask=part_mask, keys=keys,
            round_idx=state.round_idx, counts=data.train.counts,
            server_data=server_data, comp_state=state.comp_state)
        with jax.named_scope(tracing.AGGREGATE):
            new_state = RoundState(global_params=new_global,
                                   scores=new_scores,
                                   round_idx=state.round_idx + 1,
                                   key=state.key, comp_state=new_comp)
        return new_state, metrics

    def _multi_round(self, state: RoundState, data: FederatedDataset):
        """``rounds_per_call`` rounds as one fused scanned program."""
        def body(s, _):
            return self._round_body(s, data)
        return jax.lax.scan(body, state, None,
                            length=self.rounds_per_call)

    def _global_eval_impl(self, params, gx, gy):
        return self.program.eval_fn(params, gx, gy)

    # ------------------------------------------------------------------- API
    def run_round(self, state: RoundState, data: FederatedDataset):
        self._dispatched += 1
        with tracing.span(tracing.ROUND, step=self._dispatched):
            return self._round_fn(state, data)

    def compile_driver(self, state: RoundState, data: FederatedDataset):
        """Compile, ahead of time, the program that ``run`` dispatches
        for a full chunk (the scanned driver when ``rounds_per_call >
        1``). JAX's in-memory cache then serves ``run``'s calls with the
        same argument types, so this splits compilation from the first
        round and exposes the compiled HLO and its memory analysis."""
        fn = self._scan_fn or self._round_fn
        return fn.lower(state, data).compile()

    def global_accuracy(self, state: RoundState, data: FederatedDataset,
                        max_samples: int = 2048) -> float:
        with tracing.span(tracing.GLOBAL_EVAL):
            return float(self._global_eval(state.global_params,
                                           data.global_x[:max_samples],
                                           data.global_y[:max_samples]))

    def run(self, key, data: FederatedDataset, rounds: Optional[int] = None,
            eval_every: int = 1, verbose: bool = False,
            state: Optional[RoundState] = None, ckpt=None,
            should_stop: Optional[Callable[[], bool]] = None):
        """Full training loop; returns (final_state, history dict).

        With ``rounds_per_call > 1`` the steady state runs through the
        scanned driver — per-round scalar metrics still cover every
        round (the scan stacks them), global accuracy is evaluated at
        driver-call boundaries. A remainder of ``rounds %
        rounds_per_call`` rounds falls back to the single-round driver
        (a second compiled program, still one trace each).

        Durability (DESIGN.md §9): pass ``state`` (e.g. from
        ``restore_checkpoint``) to resume — ``rounds`` is the *total*
        target, so a state at round k runs k..rounds and the result is
        bit-identical to an uninterrupted run (the round body re-derives
        every key from the carried ``state.key`` and ``round_idx``).
        ``ckpt`` is a :class:`~repro.checkpoint.CheckpointManager` whose
        ``save_every`` cadence is honoured at driver-call boundaries;
        ``should_stop()`` is polled between driver calls so a signal
        handler can end the loop cleanly (the caller saves the returned
        state).
        """
        rounds = rounds if rounds is not None else self.fed.rounds
        if state is None:
            state = self.init(key)
        history = {"round": [], "global_accuracy": [], "local_loss": [],
                   "malicious_weight": []}
        programs_used = set()
        done = int(state.round_idx)
        if ckpt is not None and ckpt.read_manifest() is None:
            ckpt.write_manifest(self.manifest())
        while done < rounds:
            if should_stop is not None and should_stop():
                break
            with tracing.span(tracing.ROUND, step=done):
                if (self._scan_fn is not None
                        and rounds - done >= self.rounds_per_call):
                    state, chunk = self._scan_fn(state, data)
                    programs_used.add("scan")
                    step = self.rounds_per_call
                    metrics = {k: v[-1] for k, v in chunk.items()}
                else:
                    state, metrics = self._round_fn(state, data)
                    programs_used.add("single")
                    step = 1
            done += step
            if ckpt is not None:
                with tracing.span(tracing.CHECKPOINT):
                    ckpt.maybe_save(done, state)
            if done % eval_every == 0 or done >= rounds or step > 1:
                ga = self.global_accuracy(state, data)
                history["round"].append(done)
                history["global_accuracy"].append(ga)
                history["local_loss"].append(float(metrics["local_loss"]))
                history["malicious_weight"].append(
                    float(metrics["malicious_weight"]))
                if verbose:
                    print(f"round {done:4d}  acc={ga:.4f}  "
                          f"loss={float(metrics['local_loss']):.4f}  "
                          f"mal_w={float(metrics['malicious_weight']):.4f}")
        if rounds > 1 and self.num_traces > max(1, len(programs_used)):
            raise RuntimeError(
                f"round engine retraced: {self.num_traces} body traces "
                f"over {rounds} rounds across {len(programs_used)} "
                "compiled driver(s) — strategy resolution must stay "
                "pre-trace")
        return state, history
