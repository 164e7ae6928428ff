"""Aggregation schemes + attacks + selection."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.aggregation import (
    accuracy_based_weights, aggregate_models, fedavg_weights)
from repro.core.attacks import apply_attacks
from repro.core.selection import rb_schedule, select_testers


def _stack(n, key=0, shapes=((3, 4), (5,))):
    ks = jax.random.split(jax.random.PRNGKey(key), len(shapes))
    return {f"p{i}": jax.random.normal(k, (n,) + s)
            for i, (k, s) in enumerate(zip(ks, shapes))}


def test_fedavg_weights_proportional_to_counts():
    w = np.asarray(fedavg_weights(jnp.array([10, 30, 60])))
    np.testing.assert_allclose(w, [0.1, 0.3, 0.6], atol=1e-6)


@settings(max_examples=40, deadline=None)
@given(accs=st.lists(st.floats(0, 1), min_size=2, max_size=8))
def test_accuracy_weights_simplex(accs):
    w = np.asarray(accuracy_based_weights(jnp.asarray(accs)))
    assert (w >= 0).all()
    np.testing.assert_allclose(w.sum(), 1.0, atol=1e-5)


def test_aggregate_linearity():
    stacked = _stack(4)
    w = jnp.array([0.1, 0.2, 0.3, 0.4])
    agg = aggregate_models(stacked, w, impl="naive")
    manual = jax.tree_util.tree_map(
        lambda x: jnp.einsum("c,c...->...", w, x), stacked)
    for a, b in zip(jax.tree_util.tree_leaves(agg),
                    jax.tree_util.tree_leaves(manual)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_attack_replaces_only_last_m():
    stacked = _stack(5)
    global_params = jax.tree_util.tree_map(lambda x: x[0] * 0, stacked)
    out = apply_attacks(jax.random.PRNGKey(0), stacked, global_params,
                        num_malicious=2, attack="random_weights")
    for name in stacked:
        np.testing.assert_allclose(np.asarray(out[name][:3]),
                                   np.asarray(stacked[name][:3]))
        assert np.abs(np.asarray(out[name][3:])
                      - np.asarray(stacked[name][3:])).max() > 1e-3


def test_sign_flip_is_gradient_ascent():
    stacked = _stack(2)
    gp = jax.tree_util.tree_map(lambda x: jnp.zeros_like(x[0]), stacked)
    out = apply_attacks(jax.random.PRNGKey(0), stacked, gp,
                        num_malicious=1, attack="sign_flip", scale=1.0)
    for name in stacked:
        np.testing.assert_allclose(np.asarray(out[name][1]),
                                   -np.asarray(stacked[name][1]), atol=1e-5)


def test_none_attack_identity():
    stacked = _stack(3)
    gp = jax.tree_util.tree_map(lambda x: x[0], stacked)
    out = apply_attacks(jax.random.PRNGKey(0), stacked, gp,
                        num_malicious=2, attack="none")
    for name in stacked:
        np.testing.assert_allclose(np.asarray(out[name]),
                                   np.asarray(stacked[name]))


def test_tester_rotation():
    key = jax.random.PRNGKey(0)
    t1 = set(np.asarray(select_testers(key, 20, 5, 0)).tolist())
    t2 = set(np.asarray(select_testers(key, 20, 5, 1)).tolist())
    assert len(t1) == 5 and len(t2) == 5
    assert t1 != t2     # different rounds, (almost surely) different sets


def test_rb_schedule_accounting():
    sched = rb_schedule(np.array([2, 7]), num_users=10,
                        model_bytes=1000, acc_report_bytes=4)
    assert sched["num_slots"] == 10            # one orthogonal RB per user
    # 8 non-testers send the model; 2 testers send model + 10 accuracies
    assert sched["uplink_bytes"] == 8 * 1000 + 2 * (1000 + 40)
    # every non-tester's model reaches both testers over D2D
    assert sched["d2d_bytes"] == 1000 * 8 * 2
    users = [s["user"] for s in sched["slots"]]
    assert sorted(users) == list(range(10))
    # testers transmit in the last slots (Alg. 1 lines 10-12)
    assert {s["user"] for s in sched["slots"][-2:]} == {2, 7}
