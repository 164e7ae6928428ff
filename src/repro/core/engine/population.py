"""Population tier: cohort-sampled rounds that never replicate [N, D].

The dense backends materialise all N client models per device — the
O(N) replication wall (``benchmarks/bench_comm.py`` prices it at
(N−1)×model for ring and N×model peak for allgather). This tier rides
the observation that a FedTest round only ever *computes* on the
sampled cohort: per-round Bernoulli sampling (the existing
``participation_mask``) selects C ≪ N clients, and every non-sampled
client already has fully-defined free semantics — zero aggregation
weight (``renormalize_over_subset``), frozen score
(``update_scores``'s ``client_mask``), masked tester row, and a
cross-test column that equals the *global* model's accuracy (a
non-participant transmits nothing, so whoever evaluates its slot sees
the stale global copy — exactly what ``mask_models`` produces on the
dense backends).

So the round runs on a gathered ``[C, ...]`` model stack
(:class:`CohortModels`) while population state stays a dense ``[N]``
``ScoreState`` that only the cohort's rows touch:

* **gather**  — ``cohort_from_mask`` turns the round's participation
  mask into cohort slot indices; training batches and the model stack
  are gathered to ``[C]``, never broadcast to ``[N]``.
* **compute** — the unchanged :class:`RoundProgram` drives
  :class:`PopulationBackend`: vmapped local training / per-slot
  attacks over ``[C]``, cross-testing streamed through
  :func:`~repro.core.cross_testing.cross_test_tiled` in
  ``[K, block_C]`` tiles, aggregation as a fused weighted sum over the
  cohort stack (bitwise equal to the full-population sum because every
  other summand has weight exactly 0).
* **scatter** — cohort columns are scattered into a global-accuracy
  base matrix and cohort losses into zeros, reconstructing the full
  replicated ``[K, N]`` / ``[N]`` arrays the program scores — bitwise
  identical to the dense ``local`` backend (``tests/test_population.py``
  pins weights, scores, trust and malicious_weight), so convergence
  *and* adversarial suppression carry over by construction, at
  per-round cost flat in N (``benchmarks/bench_population.py``).

Sharding: with a ``mesh``, the cohort axis is annotated with
``with_sharding_constraint`` so GSPMD splits the [C] stack, batches and
eval tiles across a ``clients`` mesh axis — the multi-device smoke in
CI. The Pallas aggregation kernels cannot be partitioned by GSPMD, so
there each device reduces its slice of the cohort and one psum adds the
partial sums. Cross-device reductions are not bitwise-stable, so the parity
matrix runs unsharded; the sharded path is gated on suppression
(``--assert-malicious-below``), not bit-equality. DESIGN.md §11.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.cross_testing import CROSSTEST_IMPLS, cross_test_tiled
from repro.core.engine.backends import ExchangeBackend, _flatten_updates
from repro.core.engine.driver import FederatedTrainer, RoundState
from repro.core.engine.program import round_keys
from repro.kernels.weighted_aggregate import aggregate_pytree
from repro.utils import tracing
from repro.utils.pytree import tree_add_vector


def cohort_from_mask(part_mask: jnp.ndarray, capacity: int
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Round participation mask [N] -> cohort plan.

    Returns ``(idx, valid, eff_mask)``:

    * ``idx [capacity]`` — population indices of the sampled clients in
      ascending order, padded with the sentinel ``N`` for unfilled
      slots (static shape: the cohort buffer is a fixed ``capacity``
      wide so the round compiles once).
    * ``valid [capacity]`` — 1.0 where the slot holds a real client.
    * ``eff_mask [N]`` — the mask actually honoured this round: when
      the Bernoulli draw oversubscribes the buffer, clients beyond the
      first ``capacity`` sampled (in index order) are truncated back to
      non-sampled — they keep the full non-sampled semantics (zero
      weight, frozen score, masked tester row), exactly as if the
      coin had come up tails. When the draw fits, ``eff_mask`` is
      bitwise ``part_mask``, which is what the small-N parity matrix
      relies on.
    """
    n = part_mask.shape[0]
    ids = jnp.where(part_mask > 0, jnp.arange(n, dtype=jnp.int32),
                    jnp.int32(n))
    idx = jnp.sort(ids)[:capacity]
    valid = (idx < n).astype(jnp.float32)
    kept = (jnp.cumsum(part_mask) <= capacity).astype(part_mask.dtype)
    return idx, valid, part_mask * kept


class CohortModels(NamedTuple):
    """The population tier's opaque model handle: a [C] gathered stack.

    ``idx`` maps cohort slots to population indices (sentinel N =
    unfilled slot), ``valid`` flags real slots, ``global_ref`` is the
    round's broadcast source — the value every non-cohort column of the
    accuracy matrix must report.
    """

    stack: Any              # param pytree, leaves [C, ...]
    idx: jnp.ndarray        # [C] int32 population index (N = unfilled)
    valid: jnp.ndarray      # [C] float32 1/0
    global_ref: Any         # unstacked global params


class PopulationBackend(ExchangeBackend):
    """Cohort-gather exchange: compute on [C], report as [N] / [K, N].

    The :class:`RoundProgram` contract is unchanged — replicated
    population-indexed arrays cross the seam, model pytrees stay
    opaque — so every semantic step (attacks, lying testers,
    coalitions, scoring, trust) is byte-for-byte the shared code path.
    ``tx``/``ty`` arrive pre-gathered to the K tester rows (the
    population driver holds no [N, eval_batch] test stack), which is
    why ``cross_test`` ignores ``tester_ids``.
    """

    name = "population"

    def __init__(self, num_users: int, capacity: int,
                 crosstest_impl: str = "batched", *, block: int = 0,
                 mesh=None, axis: str = "clients"):
        if crosstest_impl not in CROSSTEST_IMPLS:
            raise ValueError(f"crosstest_impl must be one of "
                             f"{CROSSTEST_IMPLS}, got {crosstest_impl!r}")
        if not 1 <= capacity <= num_users:
            raise ValueError(
                f"cohort capacity must be in [1, num_users={num_users}], "
                f"got {capacity}")
        self.num_users = num_users
        self.capacity = capacity
        self.crosstest_impl = crosstest_impl
        self.block = block
        self.mesh = mesh
        self.axis = axis

    # --------------------------------------------------------- sharding
    def _constrain(self, tree):
        """Annotate leading-[C] leaves for GSPMD cohort sharding."""
        if self.mesh is None:
            return tree
        s = NamedSharding(self.mesh, P(self.axis))
        return jax.tree_util.tree_map(
            lambda t: jax.lax.with_sharding_constraint(t, s), tree)

    # --------------------------------------------------- backend protocol
    def train(self, local_train, global_params, bx, by):
        # the driver packs the cohort plan with the gathered batches:
        # bx = (idx [C], valid [C], cohort batches [C, steps, batch, ...])
        idx, valid, cx = bx
        stack = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None],
                                       (self.capacity,) + x.shape),
            global_params)
        stack = self._constrain(stack)
        cx, cy = self._constrain(cx), self._constrain(by)
        stack, loss = jax.vmap(local_train)(stack, cx, cy)
        # non-cohort losses report 0 — they are zero-masked by the
        # program's sampled-subset mean anyway, so the metric matches
        # the dense path bitwise
        losses = jnp.zeros((self.num_users,), loss.dtype
                           ).at[idx].set(loss, mode="drop")
        return CohortModels(stack, idx, valid, global_params), losses

    def _safe_idx(self, models: CohortModels) -> jnp.ndarray:
        # clamp sentinel slots to a real index for gathers; their
        # results never escape (zero weight / dropped scatters)
        return jnp.minimum(models.idx, self.num_users - 1)

    def apply_attack(self, attack, key, models, global_params, actx):
        safe = self._safe_idx(models)
        stack = jax.vmap(
            lambda p, c: attack.apply_local(key, p, global_params, c,
                                            self.num_users, actx)
        )(models.stack, safe)
        return models._replace(stack=self._constrain(stack))

    def mask_models(self, models, global_params, part_mask):
        my_part = part_mask[self._safe_idx(models)]
        stack = jax.tree_util.tree_map(
            lambda t, g: jnp.where(
                my_part.reshape((-1,) + (1,) * (t.ndim - 1)) > 0,
                t, g[None].astype(t.dtype)),
            models.stack, global_params)
        return models._replace(stack=self._constrain(stack))

    def cross_test(self, eval_fn, models, tx, ty, tester_ids):
        acc_c = cross_test_tiled(eval_fn, models.stack, tx, ty,
                                 block=self.block,
                                 impl=self.crosstest_impl)       # [K, C]
        # non-cohort columns: a client that transmitted nothing is seen
        # as the stale global copy, so its column is the tester's
        # accuracy on the *global* model — the same value the dense
        # backends produce for masked slots (vmap-vs-plain eval is
        # bitwise stable; pinned by tests/test_population.py). The full
        # [K, N] matrix is therefore bit-identical to the dense path,
        # and everything downstream of it (lies, coalition transforms,
        # scores, trust) is shared code on identical inputs.
        base = jax.vmap(lambda x, y: eval_fn(models.global_ref, x, y)
                        )(tx, ty)                                # [K]
        acc = jnp.broadcast_to(base[:, None],
                               (base.shape[0], self.num_users))
        acc = acc.at[:, models.idx].set(acc_c, mode="drop")
        return acc, None

    def updates(self, models, global_params, cache):
        raise NotImplementedError(
            "the population tier refuses to materialise the [N, D] "
            "update matrix — aggregators that need it (krum, "
            "trimmed_mean, median, the robust combine fast path) ARE "
            "the O(N) replication wall this tier exists to break. Use "
            "a score-weighted aggregator (fedtest/fedavg/...) or the "
            "dense backends.")

    def server_eval(self, eval_fn, models, sx, sy):
        def thunk():
            accs = jax.vmap(lambda p: eval_fn(p, sx, sy))(models.stack)
            base = eval_fn(models.global_ref, sx, sy)
            out = jnp.full((self.num_users,), base, accs.dtype)
            return out.at[models.idx].set(accs, mode="drop")
        return thunk

    def _cohort_sum(self, fn, *cohort_args):
        """``fn`` reduces cohort-stacked ``[C, ...]`` args to one sum.

        Pallas kernels do not partition automatically, so on a mesh each
        device runs ``fn`` on its slice of the cohort and one psum adds
        the partial sums (in f32).
        """
        if self.mesh is None:
            return fn(*cohort_args)

        def part(*args):
            return jax.tree_util.tree_map(
                lambda x: jax.lax.psum(x.astype(jnp.float32),
                                       self.axis).astype(x.dtype),
                fn(*args))

        return jax.shard_map(part, mesh=self.mesh,
                             in_specs=(P(self.axis),) * len(cohort_args),
                             out_specs=P(), check_vma=False)(*cohort_args)

    def weighted_sum(self, models, weights, global_params, impl):
        # weights is the renormalised [N] simplex with exact zeros
        # outside the (effective) cohort, so summing over the gathered
        # stack is bitwise the full-population sum; sentinel slots are
        # zeroed by `valid` (their gathered weight is a real client's).
        w = weights[self._safe_idx(models)] * models.valid
        return self._cohort_sum(
            lambda stack, w: aggregate_pytree(stack, w, impl=impl),
            models.stack, w)

    def compress_exchange(self, compressor, models, global_params,
                          comp_state, part_mask):
        # the error-feedback buffer stays population-dense [N, D] (it
        # is per-client *state*, like scores — only the cohort's rows
        # are gathered, encoded and scattered back each round;
        # DESIGN.md §12 documents the memory trade)
        safe = self._safe_idx(models)
        updates = _flatten_updates(models.stack, global_params)  # [C, D]
        state_rows = comp_state[safe]                            # [C, D]
        payloads, new_rows = jax.vmap(compressor.encode)(state_rows,
                                                         updates)
        decoded = jax.vmap(compressor.decode)(payloads)          # [C, D]
        eff = models.valid * (part_mask[safe]
                              if part_mask is not None else 1.0)
        keep = (eff > 0)[:, None]
        # masked / sentinel slots transmitted nothing: buffer rows stay
        # (scattering the gathered row back is a bitwise no-op) and the
        # decoded update is exactly zero
        new_rows = jnp.where(keep, new_rows, state_rows)
        decoded = jnp.where(keep, decoded, 0.0)
        new_state = comp_state.at[models.idx].set(new_rows, mode="drop")
        stack = jax.vmap(
            lambda v: tree_add_vector(global_params, v))(decoded)
        return (models._replace(stack=self._constrain(stack)),
                payloads, decoded, new_state)

    def compressed_sum(self, compressor, payloads, decoded, weights,
                       models, impl):
        # same zero-outside-cohort argument as weighted_sum: the [N]
        # simplex gathered to the cohort rows loses only exact-zero
        # summands
        w = weights[self._safe_idx(models)] * models.valid
        return self._cohort_sum(
            lambda p, d, w: compressor.aggregate(p, d, w, impl),
            payloads, decoded, w)


@dataclasses.dataclass
class PopulationTrainer(FederatedTrainer):
    """Single-host driver for the population tier (DESIGN.md §11).

    A :class:`FederatedTrainer` whose round body gathers the sampled
    cohort before the program runs: the full-population Bernoulli draw
    and batch-index draw are unchanged (same ``RoundKeys`` streams, so
    trajectories are comparable with the dense driver bit-for-bit at
    small N), but only the cohort's rows of the batch data are ever
    materialised. Population state — ``ScoreState``, the PRNG schedule,
    the round index — stays the dense :class:`RoundState`, so
    checkpointing, manifests and bit-identical resume are inherited
    wholesale from the durable-service machinery (DESIGN.md §9).

    ``cohort`` (0 = ``fed.cohort``, else override) is the static slot
    capacity; ``crosstest_block`` streams tester eval in
    ``[K, block_C]`` tiles; ``mesh`` shards the cohort axis via GSPMD.
    Data comes from a population provider
    (:class:`repro.data.population.DensePopulationData` /
    :class:`~repro.data.population.SyntheticPopulation`) rather than a
    materialised :class:`FederatedDataset`.
    """

    cohort: int = 0
    crosstest_block: int = 0
    mesh: Any = None
    # At C ≪ N a population-wide tester is almost never in the cohort,
    # so every report row is participation-masked and the cohort's
    # scores degenerate to zero (uniform-over-cohort aggregation — no
    # suppression). This opt-in remaps the selector's tester ids onto
    # cohort members (slot = selected id mod cohort size), recruiting
    # the round's testing committee from the active cohort. Off by
    # default: the remap changes which clients test, so it would break
    # the bitwise small-N parity with the dense selector semantics.
    testers_from_cohort: bool = False

    def __post_init__(self):
        self.capacity = self.cohort or self.fed.cohort or self.fed.num_users
        if not 1 <= self.capacity <= self.fed.num_users:
            raise ValueError(
                f"cohort={self.capacity} must be in [1, "
                f"num_users={self.fed.num_users}]")
        if self.capacity < self.fed.num_users and self.fed.participation >= 1:
            raise ValueError(
                "cohort < num_users requires participation < 1.0 — with "
                "everyone sampled every round, truncation to the cohort "
                "buffer would silently bias toward low client indices. "
                "Set FedConfig.participation ≈ cohort/num_users.")
        if self.eval_resample_every:
            raise ValueError(
                "eval_resample_every is a dense-driver feature (it draws "
                "[N, eval_batch] gather indices); the population tier "
                "gathers tester rows directly")
        super().__post_init__()
        if self.program.needs_updates:
            raise ValueError(
                f"aggregator {self.program.aggregator.name!r} needs the "
                "[N, D] update matrix — the population tier refuses it "
                "(that matrix is the replication wall). Use a "
                "score-weighted aggregator or the dense backends.")

    def _make_backend(self, impl: str):
        return PopulationBackend(self.fed.num_users, self.capacity, impl,
                                 block=self.crosstest_block,
                                 mesh=self.mesh)

    def _on_mesh(self, state: RoundState) -> RoundState:
        # a sharded round returns its state replicated over the mesh;
        # a state that starts there too keeps the compiled round's input
        # types fixed, so the second round does not retrace
        if self.mesh is None:
            return state
        return jax.device_put(state, NamedSharding(self.mesh, P()))

    def init(self, key) -> RoundState:
        return self._on_mesh(super().init(key))

    def load_state(self, state_dict: dict) -> RoundState:
        return self._on_mesh(super().load_state(state_dict))

    def _round_body(self, state: RoundState, data):
        self.num_traces += 1
        fed = self.fed
        with jax.named_scope(tracing.SELECT):
            keys = round_keys(jax.random.fold_in(state.key,
                                                 state.round_idx))
            tester_ids, part_mask = self.program.select_round(
                keys, state.round_idx, scores=state.scores.scores)
            idx, valid, eff_mask = cohort_from_mask(part_mask,
                                                    self.capacity)
            if self.testers_from_cohort:
                pop_count = jnp.maximum(jnp.sum(valid).astype(jnp.int32),
                                        1)
                tester_ids = jnp.minimum(idx[tester_ids % pop_count],
                                         fed.num_users - 1)
            safe = jnp.minimum(idx, fed.num_users - 1)
        with jax.named_scope(tracing.TRAIN):
            # the dense engine's exact batch-index draw
            # (data.pipeline.sample_client_batches), gathered down to the
            # cohort rows: the uniform draw stays [N, steps, batch]
            # (cheap — floats, not images) so keys.batch produces
            # bit-identical per-client indices, but only O(C) batch
            # *data* is gathered.
            counts = data.train_counts
            u = jax.random.uniform(keys.batch,
                                   (fed.num_users, fed.local_steps,
                                    self.train.batch_size))
            bidx = (u * counts[:, None, None]).astype(jnp.int32)[safe]
            cx, cy = data.cohort_train(safe)
            bx = jax.vmap(lambda x, i: x[i])(cx, bidx)
            by = jax.vmap(lambda y, i: y[i])(cy, bidx)
        with jax.named_scope(tracing.CROSS_TEST):
            tx, ty = data.tester_batches(tester_ids, self.eval_batch)
        with jax.named_scope(tracing.SCORE):
            server_data = data.server_batch(self.eval_batch)
        new_global, new_scores, new_comp, metrics = self.program.run(
            self.backend, state.global_params, state.scores,
            bx=(idx, valid, bx), by=by, tx=tx, ty=ty,
            tester_ids=tester_ids, part_mask=eff_mask, keys=keys,
            round_idx=state.round_idx, counts=counts,
            server_data=server_data, comp_state=state.comp_state)
        with jax.named_scope(tracing.AGGREGATE):
            new_state = RoundState(global_params=new_global,
                                   scores=new_scores,
                                   round_idx=state.round_idx + 1,
                                   key=state.key, comp_state=new_comp)
        return new_state, metrics
