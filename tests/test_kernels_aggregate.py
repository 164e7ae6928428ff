"""Weighted-aggregate kernel sweep + pytree aggregation properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.weighted_aggregate.kernel import weighted_aggregate_pallas
from repro.kernels.weighted_aggregate.ops import (
    aggregate_pytree, weighted_aggregate)
from repro.kernels.weighted_aggregate.ref import weighted_aggregate_ref


@pytest.mark.parametrize("C,M,bm", [(4, 1024, 256), (20, 4096, 1024),
                                    (3, 511, 128), (1, 64, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_matches_ref(C, M, bm, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (C, M),
                          jnp.float32).astype(dtype)
    w = jax.random.uniform(jax.random.PRNGKey(1), (C,))
    ref = weighted_aggregate_ref(x, w)
    out = weighted_aggregate(x, w, impl="pallas", block_m=bm,
                             interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


@settings(max_examples=25, deadline=None)
@given(c=st.integers(1, 8), m=st.integers(1, 300),
       seed=st.integers(0, 2 ** 16))
def test_kernel_matches_ref_hypothesis(c, m, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (c, m))
    w = jax.random.uniform(jax.random.PRNGKey(seed + 1), (c,))
    ref = weighted_aggregate_ref(x, w)
    out = weighted_aggregate(x, w, impl="pallas", block_m=64,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5,
                               rtol=1e-5)


def test_pytree_onehot_weight_selects_client():
    tree = {"a": jax.random.normal(jax.random.PRNGKey(0), (4, 3, 5)),
            "b": jax.random.normal(jax.random.PRNGKey(1), (4, 7))}
    w = jnp.array([0.0, 1.0, 0.0, 0.0])
    agg = aggregate_pytree(tree, w, impl="naive")
    np.testing.assert_allclose(np.asarray(agg["a"]),
                               np.asarray(tree["a"][1]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(agg["b"]),
                               np.asarray(tree["b"][1]), atol=1e-6)


def test_pytree_convexity_bounds():
    """A convex combination stays within the per-element min/max envelope."""
    x = jax.random.normal(jax.random.PRNGKey(2), (5, 64))
    w = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(3), (5,)))
    out = weighted_aggregate(x, w, impl="naive")
    assert (np.asarray(out) <= np.asarray(x.max(0)) + 1e-6).all()
    assert (np.asarray(out) >= np.asarray(x.min(0)) - 1e-6).all()
