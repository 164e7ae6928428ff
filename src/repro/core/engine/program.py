"""The backend-agnostic FedTest round program (Algorithm 1).

One fused round, owned exactly once (the step numbering below is the one
DESIGN.md §2 documents):

  1.  broadcast the global model to all N users            (line 15 of prev round)
  2.  every user runs ``local_steps`` optimizer steps on its own shard (line 5)
  3.  malicious users swap in attacked models              (Sec. IV)
  4.  K testers evaluate all N models on their own data    (lines 6-9)
  5.  lying testers corrupt their reports                  (Sec. V-C ablation)
  6.  the server computes scores / weights                 (line 13)
  7.  score-weighted aggregation -> new global model       (line 14)

:class:`RoundProgram` implements every step once and is parameterised by
an :class:`~repro.core.engine.backends.ExchangeBackend` that supplies
only what is genuinely topology-specific — how the N client models are
materialised (a stacked ``[N, ...]`` pytree under ``vmap``, or one model
per device under ``shard_map``), how testers see other clients' models
(vmap / ring hops / all-gather), and how per-device partials reduce
(identity / psum). Everything semantic — the participation mask, the
attack application and its :class:`AttackContext`, lying testers, the
score update (including score freezing for non-participants), the
sampled-subset renormalisation, the metrics — lives here, so the three
backends cannot drift (the equivalence matrix in
``tests/test_pod_parity.py`` pins them bit-identical).

The contract that makes this possible: the backend hands the program
*replicated* ``[N]``- / ``[K, N]``-indexed arrays (accuracy matrix,
per-client losses, flattened updates) and the program manipulates only
those plus opaque model handles it routes back through backend methods.

Steps 3, 4 and 6 are **pluggable**: the attack, tester-selection policy
and aggregator are looked up by name in :mod:`repro.strategies`
(``FedConfig.attack`` / ``.selector`` / ``.aggregator``) and resolved to
plain Python objects in the program constructor — *before* tracing — so
jit closes over static callables and one round compiles to one fused
program with no trace-time branching.

Coordinated adversaries (``FedConfig.coalition``, DESIGN.md §7) hook the
same two seams: the coalition's model attack composes into step 3
(:meth:`Coalition.compose` unions the malicious set, so the
``malicious_weight`` metric reports the coalition's aggregate weight)
and its report transform runs as step 5b on the replicated accuracy
matrix — shared code on every backend, so the three exchange backends
stay bit-identical under coalition attacks too.

Client failures (``FedConfig.fault``, DESIGN.md §9) enter as step 2b: a
:class:`~repro.strategies.base.Fault` model turns the round schedule's
``keys.fault`` stream into a ``[N]`` survival mask that is ANDed into
the participation mask after selection (:func:`compose_fault_mask`) —
dropped clients inherit the non-sampled semantics wholesale, and the
round emits a ``dropped_fraction`` metric.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.config import FedConfig, TrainConfig
from repro.core.cross_testing import make_eval_fn
from repro.core.scoring import score_weights
from repro.optim import make_optimizer
from repro.strategies.base import (
    Aggregator, AttackContext, RoundContext, uses_combine)
from repro.utils import tracing
from repro.utils.pytree import tree_add_vector


class RoundKeys(NamedTuple):
    """The per-round PRNG key bundle, one derivation for every driver.

    ``round_keys`` is the exact schedule the historical single-host
    engine used (``split(key, 4)`` then ``fold_in(key, 5)`` /
    ``fold_in(key, 6)``), so replaying a round on another backend — or
    from a host loop, as the pod driver and the parity tests do — means
    deriving this bundle from the same base key, nothing more.
    """

    batch: jnp.ndarray      # client batch sampling
    attack: jnp.ndarray     # base attack key (per-client fold downstream)
    test: jnp.ndarray       # tester selection
    lie: jnp.ndarray        # lying testers' fake reports
    agg: jnp.ndarray        # randomised aggregation strategies
    part: jnp.ndarray       # participation (client-sampling) mask
    fault: jnp.ndarray      # client-failure (fault-injection) mask


def round_keys(key) -> RoundKeys:
    """Derive the :class:`RoundKeys` bundle from a round's base key.

    New streams extend the bundle with further ``fold_in`` constants
    (``fault`` = 7) so the historical streams — and therefore every
    committed trajectory — stay bit-identical.
    """
    k_batch, k_attack, k_test, k_lie = jax.random.split(key, 4)
    return RoundKeys(batch=k_batch, attack=k_attack, test=k_test, lie=k_lie,
                     agg=jax.random.fold_in(key, 5),
                     part=jax.random.fold_in(key, 6),
                     fault=jax.random.fold_in(key, 7))


def participation_mask(key, num_users: int, participation: float
                       ) -> jnp.ndarray:
    """Per-round Bernoulli client-sampling mask ``[N]`` (1 = sampled).

    Falls back to everyone in the zero-participant corner so a round is
    always well defined. Every backend gets the mask from this one
    formula via :meth:`RoundProgram.select_round`, so the sampled
    subsets agree bit-exactly for equal keys.
    """
    bern = jax.random.bernoulli(key, participation, (num_users,))
    return jnp.where(jnp.any(bern), bern.astype(jnp.float32),
                     jnp.ones((num_users,), jnp.float32))


def compose_fault_mask(part_mask: jnp.ndarray, alive: jnp.ndarray
                       ) -> jnp.ndarray:
    """AND the fault survival mask into the participation mask (§2b).

    A dropped client is indistinguishable from a non-sampled one — it
    transmitted nothing — so the composed mask feeds the existing
    non-sampled machinery unchanged. If *every* selected client dropped,
    the faults are ignored for the round (the round must stay well
    defined; mirrors :func:`participation_mask`'s zero-participant
    fallback). One formula, applied once in :meth:`RoundProgram.run`,
    so local/ring/allgather stay bit-identical under faults.
    """
    combined = part_mask * alive
    return jnp.where(jnp.sum(combined) > 0, combined, part_mask)


def renormalize_over_subset(weights: jnp.ndarray, part_mask: jnp.ndarray
                            ) -> jnp.ndarray:
    """Zero non-participants and renormalise the simplex over the subset.

    If the sampled subset got zero total weight, fall back to uniform
    over it. One formula, applied once in :meth:`RoundProgram.run`, so
    the sampled-subset renormalisation cannot drift between backends
    (the equivalence matrix pins the resulting zero pattern and sums).
    """
    w = weights * part_mask
    total = jnp.sum(w)
    return jnp.where(total > 1e-12, w / jnp.maximum(total, 1e-12),
                     part_mask / jnp.sum(part_mask))


def pairwise_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Sum of a vector by a pairwise tree that this code fixes.

    ``jnp.sum`` leaves the order of the additions to XLA, which picks it
    per fusion: the dense and the cohort engine then add the same
    per-client values in different orders and differ in the last bit.
    """
    while x.shape[0] > 1:
        if x.shape[0] % 2:
            x = jnp.concatenate([x, jnp.zeros((1,), x.dtype)])
        half = x.shape[0] // 2
        x = x[:half] + x[half:]
    return x[0]


def aggregator_defaults(fed: FedConfig, use_trust: bool = False
                        ) -> Dict[str, Any]:
    """Engine-derived default kwargs offered to aggregator constructors.

    Each aggregator picks up only the fields its ``__init__`` accepts
    (``Registry.build`` filters by signature): ``fedtest`` takes the
    scoring knobs, ``krum`` takes ``num_byzantine`` (the defender's
    assumed f, defaulted to the scenario's ``num_malicious``), the rest
    need nothing.
    """
    return dict(score_power=fed.score_power,
                score_decay=fed.score_decay,
                power_warmup_rounds=fed.power_warmup_rounds,
                use_trust=use_trust,
                num_byzantine=fed.num_malicious)


def resolve_strategies(fed: FedConfig, use_trust: bool = False,
                       aggregator=None):
    """Name -> object resolution for (aggregator, attack, selector).

    ``aggregator`` — optional override: a registry name or an already
    constructed :class:`Aggregator` instance (the pod builders accept
    both); defaults to ``fed.aggregator``.
    """
    # package import (not just .base) so the registries are populated
    from repro.strategies import AGGREGATORS, ATTACKS, SELECTORS
    if isinstance(aggregator, Aggregator):
        agg = aggregator
    else:
        agg = AGGREGATORS.build(aggregator or fed.aggregator,
                                fed.strategy_kwargs("aggregator"),
                                aggregator_defaults(fed, use_trust))
    atk = ATTACKS.build(fed.attack, fed.strategy_kwargs("attack"),
                        dict(num_malicious=fed.num_malicious,
                             scale=fed.attack_scale))
    # seed default: schedule-based selectors (coverage) derive their
    # per-cycle shuffle from the run seed, not a fixed key
    sel = SELECTORS.build(fed.selector, fed.strategy_kwargs("selector"),
                          dict(seed=fed.seed))
    return agg, atk, sel


def resolve_fault(fed: FedConfig):
    """Name -> object resolution for ``fed.fault`` (DESIGN.md §9).

    ``rate`` defaults to ``fed.fault_rate`` (silently dropped when the
    fault model's constructor does not accept it — ``targeted`` and
    ``straggler_deadline`` have their own knobs).
    """
    from repro.strategies import FAULTS
    return FAULTS.build(fed.fault, fed.strategy_kwargs("fault"),
                        dict(rate=fed.fault_rate))


def resolve_coalition(fed: FedConfig):
    """Name -> object resolution for ``fed.coalition`` (DESIGN.md §7).

    ``size`` defaults to ``fed.coalition_size`` and the total model-
    attack ``scale`` to ``fed.attack_scale`` (each silently dropped when
    the coalition's constructor does not accept it).
    """
    from repro.strategies import COALITIONS
    return COALITIONS.build(fed.coalition,
                            fed.strategy_kwargs("coalition"),
                            dict(size=fed.coalition_size,
                                 scale=fed.attack_scale))


def flat_update_dim(model) -> int:
    """Static width D of the flattened update vector.

    Matches ``_flatten_updates``'s layout (leaf order, full ravel) by
    construction — both walk the same param pytree — and is derived
    abstractly (``eval_shape``), so no model is ever materialised at
    build time.
    """
    import math
    shapes = jax.eval_shape(model.init,
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    return sum(math.prod(leaf.shape) or 1
               for leaf in jax.tree_util.tree_leaves(shapes))


def resolve_compressor(fed: FedConfig, model):
    """Name -> object resolution for ``fed.compressor`` (DESIGN.md §12).

    The engine injects the static flat update width ``dim`` so payload
    shapes (top-k count, chunk grid, factor ranks) are fixed at build
    time and the traced round stays retrace-free.
    """
    from repro.strategies import COMPRESSORS
    return COMPRESSORS.build(fed.compressor,
                             fed.strategy_kwargs("compressor"),
                             dict(dim=flat_update_dim(model)))


def init_comp_state(fed: FedConfig, model):
    """Initial ``[N, D]`` error-feedback buffer; ``None`` when the
    exchange is uncompressed (the seam is statically disabled, so the
    state is an empty pytree that costs nothing to thread)."""
    if fed.compressor == "identity":
        return None
    return resolve_compressor(fed, model).init_state(fed.num_users)


class RoundProgram:
    """Steps 1-7 of the FedTest round, once, for every exchange backend.

    Everything pluggable or derivable is resolved here, pre-trace: the
    strategy objects, the optimizer, the (single, shared) eval function,
    the static malicious placement, and the combine-fast-path flags. A
    jitted round closes over this object; ``FederatedTrainer.num_traces``
    and its pod analogue count retraces — steady-state training must
    keep one trace per compiled driver.
    """

    def __init__(self, model, fed: FedConfig, train_cfg: TrainConfig, *,
                 use_trust: bool = False, agg_impl: str = "auto",
                 batch_builder: Optional[Callable] = None,
                 aggregator=None):
        self.model = model
        self.train_model = model.for_training()
        self.fed = fed
        self.train_cfg = train_cfg
        self.agg_impl = agg_impl
        self.batch_builder = batch_builder
        self.opt = make_optimizer(train_cfg)
        # one eval fn, built once, shared by cross-testing, server-side
        # eval and the drivers' global-accuracy closures
        self.eval_fn = make_eval_fn(model)
        self.aggregator, self.attack, self.selector = resolve_strategies(
            fed, use_trust, aggregator=aggregator)
        # legacy selectors predate the scores= keyword — inspect once,
        # pre-trace, and only forward scores to policies that take it
        import inspect
        self._selector_takes_scores = ("scores" in inspect.signature(
            self.selector.select).parameters)
        # coordinated adversaries (DESIGN.md §7): the coalition's model
        # attack composes into the attack seam (member ∪ malicious set),
        # its report transform runs as step 5b; both resolved pre-trace.
        self.coalition = resolve_coalition(fed)
        self.coalition_active = self.coalition.active
        if self.coalition_active:
            self.attack = self.coalition.compose(self.attack,
                                                 fed.num_users)
        # a non-None combine hook routes aggregation through the
        # per-coordinate fast path; both checks are static Python, so the
        # jitted round never branches on them at trace time.
        self.uses_combine = uses_combine(self.aggregator)
        self.needs_updates = (self.aggregator.needs_updates
                              or self.uses_combine)
        self.malicious_idx = self.attack.malicious_indices(fed.num_users)
        self.malicious_mask = self.attack.malicious_mask(fed.num_users)
        self.use_participation = fed.participation < 1.0
        # fault injection (DESIGN.md §9): resolved pre-trace like every
        # strategy; the static flag keeps honest rounds branch-free.
        self.fault = resolve_fault(fed)
        self.use_faults = fed.fault != "none"
        # compressed exchange (DESIGN.md §12): 'identity' statically
        # disables the seam — the default round is byte-identical to the
        # uncompressed engine, not merely equivalent (reconstructing
        # g + (m - g) in f32 would not be bitwise m).
        self.use_compression = fed.compressor != "identity"
        self.compressor = (resolve_compressor(fed, model)
                           if self.use_compression else None)

    # ---------------------------------------------------------- local phase
    def batchify(self, bx, by) -> Dict[str, jnp.ndarray]:
        if self.batch_builder is not None:
            return self.batch_builder(bx, by)
        if self.model.cfg.family in ("cnn", "mlp"):
            return {"images": bx, "labels": by}
        return {"tokens": bx, "labels": by}

    def local_train(self, params, bx, by):
        """One client's local phase: ``local_steps`` optimizer steps.

        Backends drive this per client — ``vmap`` over the stacked axis
        on the local backend, directly on each device's shard on the pod
        backends — so the local-training math is shared by construction.
        """
        opt_state = self.opt.init(params)

        def step(carry, xb_yb):
            params, opt_state = carry
            xb, yb = xb_yb
            (loss, _), grads = jax.value_and_grad(
                self.train_model.loss, has_aux=True)(params,
                                               self.batchify(xb, yb))
            params, opt_state = self.opt.update(grads, opt_state, params)
            return (params, opt_state), loss

        (params, _), losses = jax.lax.scan(step, (params, opt_state),
                                           (bx, by))
        return params, jnp.mean(losses)

    # ------------------------------------------------------- round plumbing
    def select_round(self, keys: RoundKeys, round_idx, scores=None):
        """Per-round tester ids [K] and participation mask [N].

        Shared by every driver (traced on both engines), so tester sets
        and sampled subsets agree bit-exactly for equal keys. ``scores``
        is the ``[N]`` moving-average score vector entering the round —
        replicated on every backend — consumed by score-aware selectors
        (``score_weighted``); score-oblivious policies ignore it. The
        mask is all-ones when ``participation == 1`` — :meth:`run`
        branches on the static config flag, never on the mask values.
        """
        fed = self.fed
        if self._selector_takes_scores:
            tester_ids = self.selector.select(keys.test, fed.num_users,
                                              fed.num_testers, round_idx,
                                              scores=scores)
        else:
            tester_ids = self.selector.select(keys.test, fed.num_users,
                                              fed.num_testers, round_idx)
        if self.use_participation:
            part_mask = participation_mask(keys.part, fed.num_users,
                                           fed.participation)
        else:
            part_mask = jnp.ones((fed.num_users,), jnp.float32)
        return tester_ids, part_mask

    # ------------------------------------------------------------ the round
    def run(self, backend, global_params, scores, *, bx, by, tx, ty,
            tester_ids, part_mask, keys: RoundKeys, round_idx, counts,
            server_data=None, comp_state=None):
        """One FedTest round on ``backend``; steps 1-7, owned here.

        ``bx, by`` are the round's training batches and ``tx, ty`` the
        local test shards, in the backend's client layout (stacked
        ``[N, ...]`` locally, per-device slices under ``shard_map``).
        ``tester_ids`` / ``part_mask`` come from :meth:`select_round`,
        ``keys`` from :func:`round_keys`. ``comp_state`` is the
        replicated ``[N, D]`` error-feedback buffer when the exchange is
        compressed (DESIGN.md §12), ``None`` otherwise. Returns
        ``(new_global, new_scores, new_comp_state, metrics)`` — all
        replicated (``new_comp_state`` is ``None`` when uncompressed).
        """
        fed = self.fed
        pmask = part_mask if self.use_participation else None

        # 2b. fault injection (DESIGN.md §9): the survival mask from the
        # round schedule's keys.fault stream is ANDed into the
        # participation mask *after* selection — a dropped client is a
        # non-sampled client from here on (zero weight, frozen score,
        # masked tester row), so every downstream path is shared code.
        dropped_fraction = jnp.zeros(())
        if self.use_faults:
            with jax.named_scope(tracing.SELECT):
                alive = self.fault.mask(keys.fault, fed.num_users,
                                        round_idx)
                effective = compose_fault_mask(part_mask, alive)
                dropped_fraction = (
                    (jnp.sum(part_mask) - jnp.sum(effective))
                    / jnp.maximum(jnp.sum(part_mask), 1.0))
            pmask = effective

        # 1-2. broadcast + local training; losses come back as a
        # replicated [N] vector whatever the backend topology
        with jax.named_scope(tracing.TRAIN):
            models, local_loss = backend.train(self.local_train,
                                               global_params, bx, by)

        # 3. adversaries act (strategy; malicious set can live anywhere).
        # The AttackContext exposes the cross-testing signal *entering*
        # the round — the scores and the aggregation weights they imply —
        # so adaptive attacks can react to being suppressed.
        with jax.named_scope(tracing.ATTACK):
            actx = AttackContext(scores=scores.scores,
                                 weights=score_weights(scores),
                                 round_idx=round_idx)
            models = backend.apply_attack(self.attack, keys.attack, models,
                                          global_params, actx)

            # 3b. non-participants transmit nothing this round: whoever
            # evaluates their slot sees the stale global copy — attacked
            # or not, an unsampled client's model never leaves the device.
            if pmask is not None:
                models = backend.mask_models(models, global_params, pmask)

        # 3c. compressed exchange (DESIGN.md §12): each participating
        # client encodes its flat update (with error feedback banked in
        # comp_state) and every consumer from here on — cross-testing,
        # scoring, aggregation — sees only the decoded reconstruction,
        # so all backends stay bit-identical by construction. A masked
        # client transmits nothing: its buffer is untouched and its
        # decoded update is exactly zero (slot == stale global, the 3b
        # semantics).
        new_comp_state = comp_state
        comp_payloads = comp_decoded = None
        if self.use_compression:
            with jax.named_scope(tracing.EXCHANGE):
                models, comp_payloads, comp_decoded, new_comp_state = (
                    backend.compress_exchange(self.compressor, models,
                                              global_params, comp_state,
                                              pmask))

        # 4. the round's testers measure accuracies on their own data.
        # The backend returns the replicated [K, N] matrix A[k, c] (and
        # an opaque cache, e.g. the all-gathered models, that
        # ``backend.updates`` may reuse so nothing is exchanged twice).
        with jax.named_scope(tracing.CROSS_TEST):
            acc, cache = backend.cross_test(self.eval_fn, models, tx, ty,
                                            tester_ids)

        with jax.named_scope(tracing.SCORE):
            # 5. lying testers (Sec. V-C): users with id < lying_testers
            # report uniform random accuracies whenever selected to test.
            # The matrix is replicated, so this works on every backend.
            if fed.lying_testers:
                lies = jax.random.uniform(keys.lie, acc.shape)
                liar_rows = (tester_ids < fed.lying_testers)[:, None]
                acc = jnp.where(liar_rows, lies, acc)

            # 5b. coalition report-space attack (DESIGN.md §7): members
            # selected as testers rewrite their rows of the replicated
            # matrix (mutual boost + targeted defamation driven by the
            # AttackContext scores). Replicated matrix -> shared code ->
            # bit-identical on every backend.
            if self.coalition_active:
                acc = self.coalition.transform_reports(
                    jax.random.fold_in(keys.lie, 1), acc, tester_ids, actx)

            # 6. weights via the aggregation strategy
            server_eval = None
            if self.aggregator.needs_server_eval:
                if server_data is None:
                    raise ValueError(
                        f"aggregator {self.aggregator.name!r} needs a "
                        "server-side eval set; pass server_data=(sx, sy)")
                sx, sy = server_data
                server_eval = backend.server_eval(self.eval_fn, models, sx, sy)
            # the [N, D] update matrix is materialised at most once per round
            # and shared between ctx.updates consumers and the combine path
            updates = (backend.updates(models, global_params, cache)
                       if self.needs_updates else None)
            ctx = RoundContext(acc_matrix=acc, tester_ids=tester_ids,
                               scores=scores, counts=counts,
                               round_idx=round_idx, key=keys.agg,
                               updates=updates, server_eval=server_eval,
                               participation=pmask,
                               report_mask=(pmask[tester_ids]
                                            if pmask is not None else None))
            # non-sampled clients' scores freeze inside update_scores
            # (client_mask=ctx.participation): no evidence about an absent
            # client — a suppressed attacker stays suppressed while it sits
            # out. One code path for every backend.
            new_scores = self.aggregator.update_scores(ctx)
            ctx = ctx._replace(scores=new_scores)
            weights = self.aggregator.weights(ctx)
            if pmask is not None:
                weights = renormalize_over_subset(weights, pmask)

        with jax.named_scope(tracing.AGGREGATE):
            # 7. aggregation -> new global model: the per-coordinate combine
            # fast path runs replicated on the [N, D] matrix (identical on
            # every backend); the weights path reduces through the backend
            # (fused weighted sum locally, one psum on the pod).
            if self.uses_combine:
                new_global = tree_add_vector(
                    global_params, self.aggregator.combine(ctx, updates))
            elif self.use_compression:
                # compressed weights path: aggregate in *update space* from
                # the wire representation (the fused dequant_aggregate
                # kernel for int8 — the f32 [C, D] stack never hits HBM),
                # then one tree_add_vector back into model space. Same
                # formula on every backend (local kernel == pod psum, the
                # §3 replication contract).
                new_global = tree_add_vector(
                    global_params,
                    backend.compressed_sum(self.compressor, comp_payloads,
                                           comp_decoded, weights, models,
                                           self.agg_impl))
            else:
                new_global = backend.weighted_sum(models, weights,
                                                  global_params, self.agg_impl)

            # the malicious index set comes from the attack strategy, so the
            # metric stays correct for any placement of the attackers.
            mal_w = (jnp.sum(weights * self.malicious_mask)
                     if self.malicious_idx else jnp.zeros(()))
            # losses of non-participants are discarded work (their training
            # never left the device) — the mean runs over the sampled subset
            metrics = {
                "local_loss": (pairwise_sum(local_loss * pmask)
                               / jnp.maximum(jnp.sum(pmask), 1)
                               if pmask is not None
                               else jnp.mean(local_loss)),
                "acc_matrix_mean": jnp.mean(acc),
                "weights": weights,
                "malicious_weight": mal_w,
                "scores": new_scores.scores,
                "participation_rate": (jnp.mean(pmask)
                                       if pmask is not None
                                       else jnp.ones(())),
                # fraction of *selected* clients lost to faults this round
                # (0 under fault='none'; DESIGN.md §9)
                "dropped_fraction": dropped_fraction,
            }
        return new_global, new_scores, new_comp_state, metrics
