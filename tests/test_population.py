"""Population tier vs dense engine: bitwise equivalence + semantics.

The cohort engine's whole claim (DESIGN.md §11) is that gathering the
sampled cohort and scattering back is *invisible*: at small N the
population trainer must produce bit-identical trajectories to the dense
:class:`FederatedTrainer` — params, scores, weights, malicious weight,
losses and the accuracy matrix — under attacks, coalitions and partial
participation. These tests pin that matrix, the tiled cross-testing
path, mid-trajectory checkpoint resume, the cohort-buffer truncation
semantics, and the loud-refusal surface (oversized cohorts, update-
matrix aggregators, dense-only features).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.config import FedConfig, TrainConfig
from repro.configs import get_config, scenario_for_population
from repro.core.engine import (FederatedTrainer, PopulationTrainer,
                               cohort_from_mask)
from repro.core.engine.program import pairwise_sum
from repro.data import MNIST_LIKE, make_federated_image_dataset
from repro.data.population import DensePopulationData
from repro.models import build_model

N = 8


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("fedtest-cnn-mnist").replace(cnn_channels=(4, 8, 8),
                                                  cnn_hidden=16)
    model = build_model(cfg)
    data = make_federated_image_dataset(MNIST_LIKE, N, num_samples=800,
                                        global_test=200, seed=0)
    tc = TrainConfig(optimizer="sgd", lr=0.1, schedule="constant",
                     batch_size=8, grad_clip=0.0, remat=False)
    return model, data, tc


def _tree_equal(a, b) -> bool:
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


# the equivalence matrix: attack/coalition regimes × sampling rates
CASES = {
    "no_attack": dict(attack="none"),
    "sign_flip": dict(attack="sign_flip", num_malicious=2),
    "mutual_boost": dict(attack="random_weights", num_malicious=2,
                         coalition="mutual_boost", coalition_size=2,
                         aggregator_kwargs={"use_trust": True,
                                            "trust_decay": 0.3,
                                            "report_clip": 0.2}),
}


@pytest.mark.parametrize("participation", [0.5, 0.75])
@pytest.mark.parametrize("case", sorted(CASES))
def test_cohort_matches_dense_bitwise(setup, case, participation):
    model, data, tc = setup
    fed = FedConfig(num_users=N, num_testers=3, local_steps=2,
                    participation=participation, cohort=N, **CASES[case])
    dense = FederatedTrainer(model, fed, tc, eval_batch=32)
    pop = PopulationTrainer(model, fed, tc, eval_batch=32)
    key = jax.random.PRNGKey(42)
    sd, sp = dense.init(key), pop.init(key)
    pd = DensePopulationData(data)
    for r in range(3):
        sd, md = dense.run_round(sd, data)
        sp, mp = pop.run_round(sp, pd)
        for name, a, b in [
            ("params", sd.global_params, sp.global_params),
            ("scores", sd.scores, sp.scores),
            ("weights", md["weights"], mp["weights"]),
            ("malicious_weight", md["malicious_weight"],
             mp["malicious_weight"]),
            ("local_loss", md["local_loss"], mp["local_loss"]),
            ("acc_matrix_mean", md["acc_matrix_mean"],
             mp["acc_matrix_mean"]),
        ]:
            assert _tree_equal(a, b), (
                f"{case} participation={participation} round {r}: "
                f"{name} diverged from the dense engine")


@pytest.mark.parametrize("n", [1, 5, 8, 20])
def test_pairwise_sum_adds_in_a_fixed_tree(n):
    """The sampled-subset loss is summed in an order the code fixes, so
    both engines add their per-client losses alike: halves added
    elementwise, a zero appended to an odd length."""
    x = jax.random.normal(jax.random.PRNGKey(n), (n,))
    want = np.asarray(x)
    while len(want) > 1:
        if len(want) % 2:
            want = np.append(want, np.float32(0))
        want = want[:len(want) // 2] + want[len(want) // 2:]
    got = jax.jit(pairwise_sum)(x)
    assert np.asarray(got).view(np.int32) == want[0].view(np.int32)
    ints = jnp.arange(n, dtype=jnp.float32)
    assert float(pairwise_sum(ints)) == n * (n - 1) / 2


def test_tiled_crosstest_bitwise_matches_untiled(setup):
    model, data, tc = setup
    fed = FedConfig(num_users=N, num_testers=3, local_steps=2,
                    participation=0.75, cohort=N, attack="sign_flip",
                    num_malicious=2)
    a = PopulationTrainer(model, fed, tc, eval_batch=32)
    # block=3 does not divide C=8: exercises the wrap-padded last tile
    b = PopulationTrainer(model, fed, tc, eval_batch=32, crosstest_block=3)
    sa, sb = a.init(jax.random.PRNGKey(7)), b.init(jax.random.PRNGKey(7))
    pd = DensePopulationData(data)
    for _ in range(3):
        sa, _ = a.run_round(sa, pd)
        sb, _ = b.run_round(sb, pd)
    assert _tree_equal(sa, sb)


def test_population_checkpoint_resume_bit_identical(setup, tmp_path):
    model, data, tc = setup
    fed = FedConfig(num_users=N, num_testers=3, local_steps=2,
                    participation=0.5, cohort=4, attack="sign_flip",
                    num_malicious=2)
    pd = DensePopulationData(data)
    ref = PopulationTrainer(model, fed, tc, eval_batch=32)
    sA, _ = ref.run(jax.random.PRNGKey(0), pd, rounds=5, eval_every=5)

    mgr = CheckpointManager(str(tmp_path))
    first = PopulationTrainer(model, fed, tc, eval_batch=32)
    s2, _ = first.run(jax.random.PRNGKey(0), pd, rounds=2, eval_every=2)
    first.save_checkpoint(mgr, s2)
    fresh = PopulationTrainer(model, fed, tc, eval_batch=32)
    restored, step = fresh.restore_checkpoint(mgr)
    assert step == 2 and int(restored.round_idx) == 2
    sB, _ = fresh.run(None, pd, rounds=5, eval_every=5, state=restored)
    assert _tree_equal(sA, sB), (
        "mid-trajectory resume diverged from the uninterrupted run")


def test_testers_from_cohort_smoke(setup):
    model, data, tc = setup
    fed = FedConfig(num_users=N, num_testers=3, local_steps=1,
                    participation=0.5, cohort=4, attack="none")
    tr = PopulationTrainer(model, fed, tc, eval_batch=16,
                           testers_from_cohort=True)
    state = tr.init(jax.random.PRNGKey(1))
    pd = DensePopulationData(data)
    for _ in range(2):
        state, m = tr.run_round(state, pd)
    # cohort-recruited committees keep reports alive: the round's score
    # mass lands on the sampled clients instead of degenerating to zero
    assert float(jnp.sum(state.scores.scores)) > 0.0
    assert np.isfinite(float(m["acc_matrix_mean"]))


# ---------------------------------------------------------- cohort plan
def test_cohort_from_mask_untruncated_is_identity():
    mask = jnp.array([1., 0., 1., 1., 0., 0., 1., 0.])
    idx, valid, eff = cohort_from_mask(mask, 6)
    assert np.array_equal(np.asarray(idx), [0, 2, 3, 6, 8, 8])
    assert np.array_equal(np.asarray(valid), [1, 1, 1, 1, 0, 0])
    # when the draw fits the buffer the honoured mask IS the draw
    assert np.array_equal(np.asarray(eff), np.asarray(mask))


def test_cohort_from_mask_truncates_in_index_order():
    mask = jnp.array([1., 1., 0., 1., 1., 1.])
    idx, valid, eff = cohort_from_mask(mask, 3)
    assert np.array_equal(np.asarray(idx), [0, 1, 3])
    assert np.array_equal(np.asarray(valid), [1, 1, 1])
    # clients past the buffer revert to full non-sampled semantics
    assert np.array_equal(np.asarray(eff), [1, 1, 0, 1, 0, 0])


# --------------------------------------------------------- loud refusals
def test_cohort_larger_than_population_rejected():
    with pytest.raises(ValueError, match="cohort"):
        FedConfig(num_users=4, num_testers=2, cohort=5, participation=0.5)
    with pytest.raises(ValueError, match="cohort"):
        scenario_for_population("honest", population=4, cohort=8)


def test_cohort_with_full_participation_rejected():
    with pytest.raises(ValueError, match="participation"):
        FedConfig(num_users=8, cohort=4)


def test_coalition_indices_outside_population_rejected():
    with pytest.raises(ValueError, match="out of range"):
        FedConfig(num_users=8, num_testers=3,
                  coalition="mutual_boost",
                  coalition_kwargs={"indices": (2, 9)})


def test_population_refuses_update_matrix_aggregators(setup):
    model, _, tc = setup
    fed = FedConfig(num_users=N, num_testers=3, local_steps=2,
                    participation=0.5, cohort=4, aggregator="krum",
                    attack="none", num_malicious=2)
    with pytest.raises(ValueError, match="replication wall"):
        PopulationTrainer(model, fed, tc)


def test_population_refuses_eval_resample(setup):
    model, _, tc = setup
    fed = FedConfig(num_users=N, num_testers=3, local_steps=2,
                    participation=0.5, cohort=4, attack="none")
    with pytest.raises(ValueError, match="eval_resample"):
        PopulationTrainer(model, fed, tc, eval_resample_every=2)
