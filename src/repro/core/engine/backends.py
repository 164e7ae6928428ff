"""Exchange backends: the topology-specific third of the round engine.

The :class:`~repro.core.engine.program.RoundProgram` owns the round's
semantics; an :class:`ExchangeBackend` supplies only the mechanics that
differ by topology (DESIGN.md §3):

* ``local``     — single host: the N client models are a stacked
  ``[N, ...]`` param pytree, local training is ``vmap`` over the client
  axis, cross-testing is ``vmap`` over the stack, aggregation is the
  fused weighted sum (the ``weighted_aggregate`` Pallas kernel on TPU).
* ``ring``      — one client per device along a mesh axis under
  ``shard_map``; cross-testing rotates the models with ``lax.ppermute``
  (N-1 hops, peak memory 2x one model), the datacenter analogue of the
  paper's orthogonal-RB D2D exchange.
* ``allgather`` — the paper-faithful broadcast: every device receives
  every model at once (N-x memory), kept as the EXPERIMENTS.md §Perf
  comparison baseline; aggregators that need the ``[N, D]`` update
  matrix reuse the gathered models, so nothing is exchanged twice.

Every backend returns *replicated* ``[N]`` / ``[K, N]`` arrays to the
program (per-client losses, the accuracy matrix, flattened updates);
the pod backends replicate via ``all_gather`` and reduce the weighted
sum with one ``psum``. That contract is what lets the equivalence
matrix (``tests/test_pod_parity.py``) pin all three backends
bit-identical on weights, scores and malicious-weight trajectories.
"""
from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import FedConfig, TrainConfig
from repro.core.cross_testing import CROSSTEST_IMPLS, cross_test_accuracies
from repro.core.engine.program import RoundProgram, round_keys
from repro.kernels.weighted_aggregate import aggregate_pytree
from repro.utils.pytree import tree_add_vector


def _flatten_updates(stacked, global_params) -> jnp.ndarray:
    """[N, D] float32 matrix of flattened client updates."""
    def flat(stack, g):
        n = stack.shape[0]
        return (stack.astype(jnp.float32)
                - g.astype(jnp.float32)[None]).reshape(n, -1)
    parts = jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(flat, stacked, global_params))
    return jnp.concatenate(parts, axis=1)


class ExchangeBackend:
    """Protocol between :class:`RoundProgram` and a topology.

    ``models`` is an opaque handle the program never inspects — a
    stacked pytree on the local backend, one device's pytree inside a
    ``shard_map`` body on the pod backends. Replicated arrays cross the
    seam; model pytrees only round-trip through these methods.
    """

    name = "base"

    def train(self, local_train, global_params, bx, by
              ) -> Tuple[Any, jnp.ndarray]:
        """Broadcast + local phase -> (models, per-client losses [N])."""
        raise NotImplementedError

    def apply_attack(self, attack, key, models, global_params, actx):
        """Step 3: corrupt the malicious clients' models."""
        raise NotImplementedError

    def mask_models(self, models, global_params, part_mask):
        """Step 3b: revert non-participants' slots to the global model."""
        raise NotImplementedError

    def cross_test(self, eval_fn, models, tx, ty, tester_ids
                   ) -> Tuple[jnp.ndarray, Any]:
        """Step 4: replicated accuracy matrix [K, N] (+ reuse cache)."""
        raise NotImplementedError

    def updates(self, models, global_params, cache) -> jnp.ndarray:
        """Replicated [N, D] float32 flattened update matrix."""
        raise NotImplementedError

    def server_eval(self, eval_fn, models, sx, sy):
        """() -> [N] accuracies of every model on the server's set."""
        raise NotImplementedError

    def weighted_sum(self, models, weights, global_params, impl):
        """Step 7 weights path: sum_c w_c * model_c -> new global."""
        raise NotImplementedError

    def compress_exchange(self, compressor, models, global_params,
                          comp_state, part_mask):
        """Step 3c (DESIGN.md §12): encode each participating client's
        flat update with error feedback, reconstruct the models every
        consumer sees from the decoded payloads. Returns
        ``(models, payloads, decoded, new_comp_state)`` — payloads /
        decoded in the backend's client layout (stacked ``[N, ...]``
        locally, this device's row on the pod), ``new_comp_state``
        replicated ``[N, D]``."""
        raise NotImplementedError

    def compressed_sum(self, compressor, payloads, decoded, weights,
                       models, impl):
        """Step 7 compressed weights path: ``sum_c w_c * decoded_c``
        in update space -> flat ``[D]`` f32 aggregated update.
        ``models`` rides along for backends whose client layout needs
        remapping the replicated [N] weights (the population cohort)."""
        raise NotImplementedError


class LocalBackend(ExchangeBackend):
    """Single-host vmap backend: clients stacked on a leading [N] axis."""

    name = "local"

    def __init__(self, num_users: int, crosstest_impl: str = "batched"):
        if crosstest_impl not in CROSSTEST_IMPLS:
            raise ValueError(f"crosstest_impl must be one of "
                             f"{CROSSTEST_IMPLS}, got {crosstest_impl!r}")
        self.num_users = num_users
        self.crosstest_impl = crosstest_impl

    def train(self, local_train, global_params, bx, by):
        stacked = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None],
                                       (self.num_users,) + x.shape),
            global_params)
        return jax.vmap(local_train)(stacked, bx, by)

    def apply_attack(self, attack, key, models, global_params, actx):
        return attack.apply(key, models, global_params, actx)

    def mask_models(self, models, global_params, part_mask):
        return jax.tree_util.tree_map(
            lambda t, g: jnp.where(
                part_mask.reshape((-1,) + (1,) * (t.ndim - 1)) > 0,
                t, g[None].astype(t.dtype)),
            models, global_params)

    def cross_test(self, eval_fn, models, tx, ty, tester_ids):
        acc = cross_test_accuracies(
            lambda p, x, y: eval_fn(p, x, y), models,
            tx[tester_ids], ty[tester_ids],
            impl=self.crosstest_impl)                        # [K, N]
        return acc, None

    def updates(self, models, global_params, cache):
        return _flatten_updates(models, global_params)

    def server_eval(self, eval_fn, models, sx, sy):
        return lambda: jax.vmap(lambda p: eval_fn(p, sx, sy))(models)

    def weighted_sum(self, models, weights, global_params, impl):
        return aggregate_pytree(models, weights, impl=impl)

    def compress_exchange(self, compressor, models, global_params,
                          comp_state, part_mask):
        updates = _flatten_updates(models, global_params)       # [N, D]
        payloads, new_state = jax.vmap(compressor.encode)(comp_state,
                                                          updates)
        decoded = jax.vmap(compressor.decode)(payloads)         # [N, D]
        if part_mask is not None:
            # a masked client transmitted nothing: its error buffer
            # must not be flushed and its decoded update is exactly 0,
            # so the reconstructed slot is bitwise the stale global
            keep = (part_mask > 0)[:, None]
            new_state = jnp.where(keep, new_state, comp_state)
            decoded = jnp.where(keep, decoded, 0.0)
        models = jax.vmap(
            lambda v: tree_add_vector(global_params, v))(decoded)
        return models, payloads, decoded, new_state

    def compressed_sum(self, compressor, payloads, decoded, weights,
                       models, impl):
        return compressor.aggregate(payloads, decoded, weights, impl)


def ring_cross_test(eval_fn, my_params, tx, ty, axis: str, num_clients: int,
                    impl: str = "batched"):
    """Every device measures every client's model on its own test data.

    Returns acc_row [num_clients]: accuracy of client c's model on *my*
    local test shard. Implemented as N-1 ``ppermute`` hops around the ring
    (visiting models), so peak memory is own + visiting model.

    ``impl`` picks the hop schedule (DESIGN.md §10): ``reference`` runs
    eval-then-permute (the historical serial hop); ``batched`` issues the
    next ``ppermute`` *before* the eval so the collective overlaps with
    the hop's compute. Both read the identical pre-permute ``visiting``
    value — the dataflow is unchanged, only the issue order — so the two
    schedules are bit-identical (pinned by ``tests/test_crosstest.py``).
    """
    my_idx = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % num_clients) for i in range(num_clients)]
    overlap = impl == "batched"

    def hop(step, carry):
        visiting, acc_row = carry
        # who owned `visiting` before `step` hops reached me?
        owner = (my_idx - step) % num_clients
        if overlap:
            nxt = jax.lax.ppermute(visiting, axis, perm)
        acc = eval_fn(visiting, tx, ty)
        acc_row = acc_row.at[owner].set(acc)
        if not overlap:
            nxt = jax.lax.ppermute(visiting, axis, perm)
        return (nxt, acc_row)

    acc_row = jnp.zeros((num_clients,), jnp.float32)
    (_, acc_row) = jax.lax.fori_loop(
        0, num_clients, hop, (my_params, acc_row))
    return acc_row


class PodBackend(ExchangeBackend):
    """Shared shard_map mechanics: one client per slice of ``axis``.

    Subclasses differ only in the cross-testing exchange (how a tester
    sees the other clients' models) and in whether the gathered models
    can be reused for the update matrix.
    """

    name = "pod"

    def __init__(self, axis: str, num_clients: int,
                 crosstest_impl: str = "batched"):
        if crosstest_impl not in CROSSTEST_IMPLS:
            raise ValueError(f"crosstest_impl must be one of "
                             f"{CROSSTEST_IMPLS}, got {crosstest_impl!r}")
        self.axis = axis
        self.num_clients = num_clients
        self.crosstest_impl = crosstest_impl

    def train(self, local_train, global_params, bx, by):
        params, loss = local_train(global_params, bx, by)
        return params, jax.lax.all_gather(loss, self.axis)      # [N]

    def apply_attack(self, attack, key, models, global_params, actx):
        my_idx = jax.lax.axis_index(self.axis)
        return attack.apply_local(key, models, global_params, my_idx,
                                  self.num_clients, actx)

    def mask_models(self, models, global_params, part_mask):
        my_part = part_mask[jax.lax.axis_index(self.axis)]
        return jax.tree_util.tree_map(
            lambda p, g: jnp.where(my_part > 0, p, g.astype(p.dtype)),
            models, global_params)

    def _acc_matrix(self, acc_row, tester_ids):
        """[N] own row -> replicated [K, N] tester rows.

        One small all-gather (N^2 floats) replicates the full matrix so
        the program scores it with exactly the single-host code path —
        the drift-proofing trade the pod makes for N extra rows.
        """
        full = jax.lax.all_gather(acc_row, self.axis)           # [N, N]
        return full[tester_ids]                                 # [K, N]

    def updates(self, models, global_params, cache):
        if cache is not None:       # all-gathered models: derive, don't
            return _flatten_updates(cache, global_params)   # gather twice
        flat = jnp.concatenate([
            (p.astype(jnp.float32) - g.astype(jnp.float32)).ravel()
            for p, g in zip(jax.tree_util.tree_leaves(models),
                            jax.tree_util.tree_leaves(global_params))])
        return jax.lax.all_gather(flat, self.axis)              # [N, D]

    def server_eval(self, eval_fn, models, sx, sy):
        my_acc = eval_fn(models, sx, sy)
        return lambda: jax.lax.all_gather(my_acc, self.axis)    # [N]

    def weighted_sum(self, models, weights, global_params, impl):
        my_w = weights[jax.lax.axis_index(self.axis)]
        return jax.tree_util.tree_map(
            lambda x: jax.lax.psum(
                (x.astype(jnp.float32) * my_w), self.axis).astype(x.dtype),
            models)

    def compress_exchange(self, compressor, models, global_params,
                          comp_state, part_mask):
        my_idx = jax.lax.axis_index(self.axis)
        update = jnp.concatenate([
            (p.astype(jnp.float32) - g.astype(jnp.float32)).ravel()
            for p, g in zip(jax.tree_util.tree_leaves(models),
                            jax.tree_util.tree_leaves(global_params))])
        payload, new_row = compressor.encode(comp_state[my_idx], update)
        decoded = compressor.decode(payload)
        if part_mask is not None:
            keep = part_mask[my_idx] > 0
            new_row = jnp.where(keep, new_row, comp_state[my_idx])
            decoded = jnp.where(keep, decoded, 0.0)
        # replicate the new buffer: each device contributes exactly its
        # own row (everything else is zero), so the psum writes every
        # row exactly once — x + 0 is bitwise x, no f32 drift
        contrib = jnp.zeros_like(comp_state).at[my_idx].set(new_row)
        new_state = jax.lax.psum(contrib, self.axis)
        models = tree_add_vector(global_params, decoded)
        return models, payload, decoded, new_state

    def compressed_sum(self, compressor, payloads, decoded, weights,
                       models, impl):
        my_w = weights[jax.lax.axis_index(self.axis)]
        return jax.lax.psum(decoded * my_w, self.axis)


class RingBackend(PodBackend):
    """Ring exchange: ``ppermute`` hops, peak memory own + visiting."""

    name = "ring"

    def cross_test(self, eval_fn, models, tx, ty, tester_ids):
        acc_row = ring_cross_test(eval_fn, models, tx, ty, self.axis,
                                  self.num_clients,
                                  impl=self.crosstest_impl)
        return self._acc_matrix(acc_row, tester_ids), None


class AllgatherBackend(PodBackend):
    """Paper-faithful exchange: every tester receives all models at once
    (the RB broadcast); N-x memory, kept as the EXPERIMENTS.md §Perf
    baseline. The gathered stack is cached for the update matrix."""

    name = "allgather"

    def cross_test(self, eval_fn, models, tx, ty, tester_ids):
        everyone = jax.tree_util.tree_map(
            lambda x: jax.lax.all_gather(x, self.axis), models)  # [N, ...]
        if self.crosstest_impl == "batched":
            # one fused [N, batch] forward over the gathered stack
            acc_row = jax.vmap(lambda p: eval_fn(p, tx, ty))(everyone)
        else:
            # reference: N sequential per-client eval dispatches
            acc_row = jnp.stack([
                eval_fn(jax.tree_util.tree_map(lambda l, c=c: l[c],
                                               everyone), tx, ty)
                for c in range(self.num_clients)])
        return self._acc_matrix(acc_row, tester_ids), everyone


# --------------------------------------------------------------- builders
def make_pod_round(model, fed: FedConfig, train_cfg: TrainConfig, mesh,
                   axis: str = "clients", aggregator=None, counts=None,
                   server_data=None, exchange: str = "ring",
                   crosstest_impl: str = None):
    """Builds the shard_map FedTest round for ``mesh[axis]`` clients.

    The returned function runs the *same* :class:`RoundProgram` as the
    local backend — resolved here, pre-trace — under ``shard_map``:

      round_fn(global_params, scores, bx, by, tx, ty, key, round_idx)
        -> (new_global (replicated), new_scores, metrics)

    With a compressed exchange configured (``fed.compressor`` other
    than ``'identity'``, DESIGN.md §12) the signature grows the
    replicated ``[N, D]`` error-feedback buffer — a static build-time
    decision, so uncompressed callers are untouched:

      round_fn(global_params, scores, comp, bx, by, tx, ty, key,
               round_idx)
        -> (new_global, new_scores, new_comp (replicated), metrics)

    ``key`` is the round's base key (``fold_in(run_key, round)``; the
    program derives the :class:`RoundKeys` bundle, the tester set and
    the participation mask from it exactly like the local driver does),
    ``bx, by`` are ``[N, steps, batch, ...]`` client-sharded training
    batches and ``tx, ty`` ``[N, eval_batch, ...]`` client-sharded local
    test shards. ``aggregator`` — registry name or
    :class:`~repro.strategies.base.Aggregator` instance; defaults to
    ``fed.aggregator``. ``counts`` are the per-client sample counts
    (static host data, closed over); without them fedavg degenerates to
    uniform weighting. ``server_data`` — optional ``(sx, sy)`` replicated
    server eval set, required only by ``needs_server_eval`` aggregators.
    ``crosstest_impl`` — cross-testing dispatch model (DESIGN.md §10);
    defaults to ``fed.crosstest_impl``.
    """
    if exchange not in ("ring", "allgather"):
        raise ValueError(f"exchange must be 'ring'|'allgather', "
                         f"got {exchange!r}")
    crosstest_impl = crosstest_impl or getattr(fed, "crosstest_impl",
                                               "batched")
    if crosstest_impl not in CROSSTEST_IMPLS:
        raise ValueError(f"crosstest_impl must be one of "
                         f"{CROSSTEST_IMPLS}, got {crosstest_impl!r}")
    num_clients = mesh.shape[axis]
    if fed.num_users != num_clients:
        raise ValueError(
            f"FedConfig.num_users={fed.num_users} but mesh[{axis!r}] has "
            f"{num_clients} slices — the pod pins one client per device "
            "(refit presets with repro.configs.scenario_for_pod)")
    program = RoundProgram(model, fed, train_cfg, aggregator=aggregator)
    if program.aggregator.needs_server_eval and server_data is None:
        raise ValueError(
            f"aggregator {program.aggregator.name!r} needs a server-side "
            "eval set; pass server_data=(sx, sy) to the round builder "
            "(e.g. the FederatedDataset's server_x/server_y)")
    counts_arr = (jnp.asarray(counts, jnp.float32) if counts is not None
                  else jnp.ones((num_clients,), jnp.float32))
    server = (None if server_data is None else
              (jnp.asarray(server_data[0]), jnp.asarray(server_data[1])))
    backend_cls = RingBackend if exchange == "ring" else AllgatherBackend

    if program.use_compression:
        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(P(), P(), P(), P(axis), P(axis), P(axis), P(axis),
                      P(), P()),
            out_specs=(P(), P(), P(), P()), check_vma=False)
        def round_fn(global_params, scores, comp, bx, by, tx, ty, key,
                     round_idx):
            bx, by = bx[0], by[0]
            tx, ty = tx[0], ty[0]
            backend = backend_cls(axis, num_clients, crosstest_impl)
            keys = round_keys(key)
            tester_ids, part_mask = program.select_round(
                keys, round_idx, scores=scores.scores)
            return program.run(backend, global_params, scores, bx=bx,
                               by=by, tx=tx, ty=ty,
                               tester_ids=tester_ids,
                               part_mask=part_mask, keys=keys,
                               round_idx=round_idx, counts=counts_arr,
                               server_data=server, comp_state=comp)

        return round_fn

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(), P(), P(axis), P(axis), P(axis), P(axis), P(), P()),
        out_specs=(P(), P(), P()), check_vma=False)
    def round_fn(global_params, scores, bx, by, tx, ty, key, round_idx):
        # shard_map gives per-client leading axes of size 1 — drop them
        bx, by = bx[0], by[0]
        tx, ty = tx[0], ty[0]
        backend = backend_cls(axis, num_clients, crosstest_impl)
        keys = round_keys(key)
        tester_ids, part_mask = program.select_round(keys, round_idx,
                                                     scores=scores.scores)
        new_global, new_scores, _, metrics = program.run(
            backend, global_params, scores, bx=bx, by=by, tx=tx, ty=ty,
            tester_ids=tester_ids, part_mask=part_mask, keys=keys,
            round_idx=round_idx, counts=counts_arr, server_data=server)
        return new_global, new_scores, metrics

    return round_fn


def make_distributed_round(model, fed: FedConfig, train_cfg: TrainConfig,
                           mesh, axis: str = "clients", aggregator=None,
                           counts=None, server_data=None):
    """Ring-exchange pod round (see :func:`make_pod_round`)."""
    return make_pod_round(model, fed, train_cfg, mesh, axis, aggregator,
                          counts, server_data, exchange="ring")


def make_allgather_round(model, fed: FedConfig, train_cfg: TrainConfig,
                         mesh, axis: str = "clients", aggregator=None,
                         counts=None, server_data=None):
    """All-gather-exchange pod round (see :func:`make_pod_round`)."""
    return make_pod_round(model, fed, train_cfg, mesh, axis, aggregator,
                          counts, server_data, exchange="allgather")
