"""FedTest scoring invariants (hypothesis property tests)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.scoring import (
    combine_tester_reports, init_scores, score_weights, update_scores,
    update_tester_trust)

accs = st.lists(st.floats(0.0, 1.0), min_size=2, max_size=12)


@settings(max_examples=60, deadline=None)
@given(a=accs, power=st.sampled_from([1.0, 2.0, 4.0]),
       decay=st.floats(0.0, 0.95))
def test_weights_form_a_simplex(a, power, decay):
    n = len(a)
    state = init_scores(n)
    acc = jnp.asarray(a)[None, :]
    state = update_scores(state, acc, jnp.arange(1), power=power,
                          decay=decay, power_warmup_rounds=0)
    w = np.asarray(score_weights(state))
    assert w.shape == (n,)
    assert (w >= 0).all()
    np.testing.assert_allclose(w.sum(), 1.0, atol=1e-5)


@settings(max_examples=60, deadline=None)
@given(a=accs, power=st.sampled_from([2.0, 4.0]))
def test_weights_monotone_in_accuracy(a, power):
    """Higher measured accuracy never gets a lower weight (round 1)."""
    n = len(a)
    state = update_scores(init_scores(n), jnp.asarray(a)[None, :],
                          jnp.arange(1), power=power, decay=0.5,
                          power_warmup_rounds=0)
    w = np.asarray(score_weights(state))
    order = np.argsort(a)
    assert (np.diff(w[order]) >= -1e-6).all()


def test_power_amplifies_separation():
    """The paper's p=4 crushes weak models harder than p=1 (Sec. V-B)."""
    a = jnp.array([[0.9, 0.3]])
    w1 = np.asarray(score_weights(update_scores(
        init_scores(2), a, jnp.arange(1), power=1.0,
        power_warmup_rounds=0)))
    w4 = np.asarray(score_weights(update_scores(
        init_scores(2), a, jnp.arange(1), power=4.0,
        power_warmup_rounds=0)))
    assert w4[0] > w1[0]
    assert w4[1] < w1[1]
    # p=4 ratio is the p=1 ratio to the 4th power
    np.testing.assert_allclose(w4[1] / w4[0], (w1[1] / w1[0]) ** 4,
                               rtol=1e-4)


def test_moving_average_weights_recent_rounds_more():
    """decay<0.5: a model that turns bad quickly loses its score."""
    state = init_scores(2)
    good = jnp.array([[0.9, 0.9]])
    bad = jnp.array([[0.9, 0.05]])
    state = update_scores(state, good, jnp.arange(1), power=4.0, decay=0.3,
                          power_warmup_rounds=0)
    first = float(state.scores[1])
    state = update_scores(state, bad, jnp.arange(1), power=4.0, decay=0.3,
                          power_warmup_rounds=0)
    second = float(state.scores[1])
    assert second < 0.4 * first


def test_first_round_uses_raw_powered_accuracy():
    state = update_scores(init_scores(3), jnp.array([[0.5, 1.0, 0.0]]),
                          jnp.arange(1), power=4.0, decay=0.9,
                          power_warmup_rounds=0)
    np.testing.assert_allclose(np.asarray(state.scores),
                               [0.5 ** 4, 1.0, 0.0], atol=1e-6)


def test_power_warmup_uses_exponent_one_first():
    """Cold-start guard: early rounds score with p=1 so evaluation luck is
    not amplified (Sec. V-B adaptive-exponent direction)."""
    state = update_scores(init_scores(2), jnp.array([[0.5, 0.1]]),
                          jnp.arange(1), power=4.0, decay=0.5,
                          power_warmup_rounds=1)
    np.testing.assert_allclose(np.asarray(state.scores), [0.5, 0.1],
                               atol=1e-6)
    state = update_scores(state, jnp.array([[0.5, 0.1]]), jnp.arange(1),
                          power=4.0, decay=0.5, power_warmup_rounds=1)
    np.testing.assert_allclose(np.asarray(state.scores),
                               [0.5 * 0.5 + 0.5 * 0.5 ** 4,
                               0.5 * 0.1 + 0.5 * 0.1 ** 4], atol=1e-6)


def test_zero_scores_fall_back_to_uniform():
    state = update_scores(init_scores(4), jnp.zeros((1, 4)),
                          jnp.arange(1), power=4.0,
                          power_warmup_rounds=0)
    np.testing.assert_allclose(np.asarray(score_weights(state)),
                               np.full(4, 0.25), atol=1e-6)


def test_combine_reports_mean_and_trust():
    acc = jnp.array([[0.8, 0.2], [0.4, 0.6]])
    plain = np.asarray(combine_tester_reports(acc, jnp.array([0, 1])))
    np.testing.assert_allclose(plain, [0.6, 0.4], atol=1e-6)
    trust = jnp.array([1.0, 0.0])
    trusted = np.asarray(combine_tester_reports(acc, jnp.array([0, 1]),
                                                trust=trust))
    np.testing.assert_allclose(trusted, [0.8, 0.2], atol=1e-6)


def test_lying_tester_loses_trust():
    state = init_scores(4)
    # tester 0 reports garbage; testers 1, 2 agree
    acc = jnp.array([[1.0, 0.0, 1.0, 0.0],
                     [0.5, 0.6, 0.55, 0.6],
                     [0.52, 0.58, 0.5, 0.62]])
    state = update_tester_trust(state, acc, jnp.array([0, 1, 2]))
    trust = np.asarray(state.tester_trust)
    assert trust[0] < trust[1]
    assert trust[0] < trust[2]
