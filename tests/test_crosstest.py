"""Cross-testing fast path (DESIGN.md §10): the batched dispatch model
must be **bitwise identical** to the per-client reference loop.

Three layers of pinning:

* matrix level — ``cross_test_accuracies(impl='batched')`` equals
  ``impl='reference'`` bit-for-bit on {mlp, cnn, decoder} stacked
  params under jit;
* engine level — a full :class:`FederatedTrainer` trajectory (weights,
  scores, malicious weight) is invariant to ``crosstest_impl`` at
  participation 1.0 *and* 0.75 — the sampled-subset rows exercise the
  frozen-score (``client_mask``) and masked-tester-row (``row_mask``)
  paths through the identical matrix;
* property level — accuracies live in [0, 1]; permuting the tester
  order permutes matrix rows without moving the combined scores; a
  fully-masked tester row never moves scores no matter what it
  contains; and the eval-batch cache is bit-insensitive to hit/miss
  (cold cache == warm cache == in-trace derivation).

The pod backends (ring hop overlap, allgather vmap) are pinned by the
``crosstest_impl`` axis of ``tests/test_pod_parity.py``.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from repro.config import FedConfig, TrainConfig, reduce_for_smoke
from repro.configs import get_config
from repro.core import FederatedTrainer
from repro.core.cross_testing import (CROSSTEST_IMPLS, EvalBatchCache,
                                      cross_test_accuracies,
                                      make_eval_fn, sampled_eval_batches)
from repro.core.scoring import (combine_tester_reports, init_scores,
                                update_scores)
from repro.data import MNIST_LIKE, make_federated_image_dataset
from repro.models import build_model

K, N = 3, 4


@functools.lru_cache(maxsize=None)
def _case(arch):
    """(eval_fn, stacked_params [N,...], tx [K,B,...], ty) for one arch."""
    if arch == "decoder":
        cfg = reduce_for_smoke(get_config("qwen2-0.5b")).replace(
            dtype="float32")
        model = build_model(cfg)
        B, S = 2, 16
        tx = jax.random.randint(jax.random.PRNGKey(1), (K, B, S), 0,
                                cfg.vocab_size)
        # -1 labels exercise the valid-token mask in the LM eval
        ty = jax.random.randint(jax.random.PRNGKey(2), (K, B, S), -1,
                                cfg.vocab_size)
    else:
        arch_id = ("fedtest-mlp-mnist" if arch == "mlp"
                   else "fedtest-cnn-mnist")
        cfg = get_config(arch_id)
        cfg = (cfg.replace(mlp_hidden=(32, 32)) if arch == "mlp"
               else cfg.replace(cnn_channels=(4, 8), cnn_hidden=16))
        model = build_model(cfg)
        tx = jax.random.normal(
            jax.random.PRNGKey(1),
            (K, 16, cfg.image_size, cfg.image_size, cfg.image_channels))
        ty = jax.random.randint(jax.random.PRNGKey(2), (K, 16), 0,
                                cfg.num_classes)
    stacked = jax.vmap(model.init)(jax.random.split(jax.random.PRNGKey(0),
                                                    N))
    return make_eval_fn(model), stacked, tx, ty


# ------------------------------------------------------ matrix-level parity
@pytest.mark.parametrize("arch", ["mlp", "cnn", "decoder"])
def test_batched_matches_reference_bitwise(arch):
    eval_fn, stacked, tx, ty = _case(arch)
    mats = {}
    for impl in CROSSTEST_IMPLS:
        fn = jax.jit(lambda s, x, y, _i=impl: cross_test_accuracies(
            eval_fn, s, x, y, impl=_i))
        mats[impl] = np.asarray(fn(stacked, tx, ty))
        assert mats[impl].shape == (K, N), (arch, impl)
        assert np.all(mats[impl] >= 0.0) and np.all(mats[impl] <= 1.0)
    np.testing.assert_array_equal(mats["batched"], mats["reference"],
                                  err_msg=f"{arch}: fast path moved a bit")


def test_unknown_impl_rejected():
    eval_fn, stacked, tx, ty = _case("mlp")
    with pytest.raises(ValueError, match="crosstest impl"):
        cross_test_accuracies(eval_fn, stacked, tx, ty, impl="fused")


# ------------------------------------------------------ engine-level parity
@pytest.mark.parametrize("participation", [1.0, 0.75])
def test_trainer_trajectory_invariant_to_impl(participation):
    """Full local-backend trajectories must not depend on the dispatch
    model — at participation 0.75 the K=3 committee hits rounds where a
    selected tester is sampled out (row_mask) and non-participants'
    scores freeze (client_mask), all through the same [K, N] matrix."""
    cfg = get_config("fedtest-mlp-mnist").replace(mlp_hidden=(32,))
    model = build_model(cfg)
    tc = TrainConfig(optimizer="sgd", lr=0.1, schedule="constant",
                     batch_size=8, grad_clip=0.0, remat=False)
    data = make_federated_image_dataset(MNIST_LIKE, N, num_samples=800,
                                        global_test=128, seed=0)
    trajs = {}
    for impl in CROSSTEST_IMPLS:
        fed = FedConfig(num_users=N, num_testers=K, num_malicious=1,
                        attack="sign_flip", attack_scale=4.0,
                        participation=participation, local_steps=4,
                        crosstest_impl=impl, seed=0)
        trainer = FederatedTrainer(model, fed, tc, eval_batch=32)
        state = trainer.init(jax.random.PRNGKey(0))
        traj = []
        for _ in range(3):
            state, m = trainer.run_round(state, data)
            traj.append((np.asarray(m["weights"]),
                         np.asarray(m["scores"]),
                         np.asarray(m["malicious_weight"])))
        trajs[impl] = (traj, state)
    for r, (b, ref) in enumerate(zip(trajs["batched"][0],
                                     trajs["reference"][0])):
        for name, x, y in zip(("weights", "scores", "mal_w"), b, ref):
            np.testing.assert_array_equal(
                x, y, err_msg=f"{name} diverged at round {r} "
                              f"(participation={participation})")
    for la, lb in zip(jax.tree_util.tree_leaves(
                          trajs["batched"][1].global_params),
                      jax.tree_util.tree_leaves(
                          trajs["reference"][1].global_params)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ---------------------------------------------------------- property tests
accs = st.lists(st.floats(0.0, 1.0), min_size=N, max_size=N)


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 2 ** 16))
def test_accuracies_bounded(seed):
    eval_fn, stacked, tx, ty = _case("mlp")
    k = jax.random.PRNGKey(seed)
    tx = tx + jax.random.normal(k, tx.shape)    # arbitrary inputs
    mat = np.asarray(cross_test_accuracies(eval_fn, stacked, tx, ty))
    assert np.all(mat >= 0.0) and np.all(mat <= 1.0)
    assert np.all(np.isfinite(mat))


@settings(max_examples=60, deadline=None)
@given(rows=st.lists(accs, min_size=K, max_size=K),
       seed=st.integers(0, 2 ** 16))
def test_tester_permutation_permutes_rows_only(rows, seed):
    """Reordering the testers permutes matrix rows; the combined score
    (a tester-mean) must not move."""
    mat = jnp.asarray(rows)                         # [K, N]
    perm = np.asarray(jax.random.permutation(jax.random.PRNGKey(seed), K))
    tester_ids = jnp.arange(K)
    base = combine_tester_reports(mat, tester_ids)
    shuf = combine_tester_reports(mat[perm], tester_ids[perm])
    np.testing.assert_allclose(np.asarray(shuf), np.asarray(base),
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(mat[perm])[0],
                                  np.asarray(mat)[perm[0]])


@settings(max_examples=60, deadline=None)
@given(rows=st.lists(accs, min_size=K, max_size=K),
       garbage=st.floats(0.0, 1.0), row=st.integers(0, K - 1))
def test_fully_masked_tester_row_never_moves_scores(rows, garbage, row):
    """A tester whose row is masked out (non-reporting: sampled out or
    dropped) must not influence scores regardless of what its row says."""
    mat = jnp.asarray(rows)
    row_mask = jnp.ones((K,)).at[row].set(0.0)
    poisoned = mat.at[row].set(garbage)
    kw = dict(tester_ids=jnp.arange(K), row_mask=row_mask)
    s0 = update_scores(init_scores(N), mat, **kw)
    s1 = update_scores(init_scores(N), poisoned, **kw)
    np.testing.assert_array_equal(np.asarray(s0.scores),
                                  np.asarray(s1.scores))


_sampled = jax.jit(sampled_eval_batches, static_argnums=(2, 4))


@settings(max_examples=20, deadline=None)
@given(resample_every=st.integers(1, 4), seed=st.integers(0, 2 ** 16))
def test_eval_batch_cache_hit_miss_insensitive(resample_every, seed):
    """Cold cache, warm cache and the in-trace derivation must agree
    bitwise for every round — the cache key is the schedule bucket, the
    indices are always re-derived from the run key (FL001)."""
    data = make_federated_image_dataset(MNIST_LIKE, N, num_samples=400,
                                        global_test=64, seed=0)
    run_key = jax.random.PRNGKey(seed)
    warm = EvalBatchCache(resample_every)
    for r in range(6):
        cold = EvalBatchCache(resample_every)        # every call a miss
        cx, cy = cold.get(run_key, data.test, 8, r)
        wx, wy = warm.get(run_key, data.test, 8, r)
        sx, sy = _sampled(run_key, data.test, 8, r, resample_every)
        np.testing.assert_array_equal(np.asarray(cx), np.asarray(wx))
        np.testing.assert_array_equal(np.asarray(cy), np.asarray(wy))
        np.testing.assert_array_equal(np.asarray(wx), np.asarray(sx))
        np.testing.assert_array_equal(np.asarray(wy), np.asarray(sy))
    assert warm.misses == -(-6 // resample_every)   # one per bucket
    assert warm.hits + warm.misses == 6
