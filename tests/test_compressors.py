"""Property suite for the compressed update exchange (DESIGN.md §12).

Pins the wire-format algebra every compressor must satisfy before the
engine threads it: round-trip error bounds, the error-feedback
telescoping invariant (sum of decoded payloads + final residual ==
sum of raw updates), identity's exactness, dtype/shape preservation,
key-free determinism (FL001), trace stability across rounds, and the
fused ``dequant_aggregate`` kernel against its dequantise-then-reduce
oracle (interpret mode, so the Pallas path is exercised on CPU).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.dequant_aggregate.kernel import dequant_aggregate_pallas
from repro.kernels.dequant_aggregate.ops import dequant_aggregate
from repro.kernels.dequant_aggregate.ref import dequant_aggregate_ref
from repro.kernels.weighted_aggregate.ops import weighted_aggregate
from repro.strategies import COMPRESSORS

SPECS = [("identity", {}), ("topk", {"k": 0.05}), ("topk", {"k": 17}),
         ("int8", {}), ("int8", {"chunk": 64}),
         ("lowrank", {"rank": 2}), ("lowrank", {"rank": 4, "iters": 3})]


def build(name, kwargs, dim):
    return COMPRESSORS.build(name, kwargs, dict(dim=dim))


def make_update(dim, seed, scale=1e-2):
    return jax.random.normal(jax.random.PRNGKey(seed), (dim,),
                             jnp.float32) * scale


# ------------------------------------------------------------ registry
def test_registry_contents():
    assert {"identity", "topk", "int8", "lowrank"} <= set(
        COMPRESSORS.names())


def test_ctor_validation():
    with pytest.raises(ValueError):
        build("identity", {}, 0)
    with pytest.raises(ValueError):
        build("topk", {"k": 0.0}, 100)
    with pytest.raises(ValueError):
        build("int8", {"chunk": 0}, 100)
    with pytest.raises(ValueError):
        build("lowrank", {"rank": 0}, 100)


def test_non_vector_update_rejected():
    comp = build("identity", {}, 12)
    with pytest.raises(ValueError, match="flat"):
        comp.encode(jnp.zeros((12,)), jnp.zeros((3, 4)))


# ------------------------------------------------- shapes/dtypes/state
@pytest.mark.parametrize("name,kwargs", SPECS)
def test_shapes_dtypes_and_state(name, kwargs):
    dim = 777
    comp = build(name, kwargs, dim)
    state = comp.init_state(5)
    assert state.shape == (5, dim) and state.dtype == jnp.float32
    assert not np.asarray(state).any()
    payload, new_row = comp.encode(state[0], make_update(dim, 0))
    dec = comp.decode(payload)
    assert dec.shape == (dim,) and dec.dtype == jnp.float32
    assert new_row.shape == (dim,) and new_row.dtype == jnp.float32
    # the payload is strictly smaller than dense f32 for lossy formats
    if name != "identity":
        assert comp.payload_bytes(jax.device_get(payload)) < 4 * dim


# ------------------------------------------------------ identity exact
@settings(max_examples=12, deadline=None)
@given(dim=st.integers(1, 600), seed=st.integers(0, 2 ** 16))
def test_identity_exact_roundtrip(dim, seed):
    comp = build("identity", {}, dim)
    u = make_update(dim, seed)
    payload, residual = comp.encode(jnp.zeros((dim,), jnp.float32), u)
    np.testing.assert_array_equal(np.asarray(comp.decode(payload)),
                                  np.asarray(u))
    np.testing.assert_array_equal(np.asarray(residual), 0.0)
    # idempotent: re-encoding the decoded value round-trips bitwise
    payload2, _ = comp.encode(jnp.zeros((dim,), jnp.float32),
                              comp.decode(payload))
    np.testing.assert_array_equal(np.asarray(comp.decode(payload2)),
                                  np.asarray(u))


# ----------------------------------------------------- roundtrip error
@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 16), chunk=st.sampled_from([32, 256]))
def test_int8_roundtrip_error_bound(seed, chunk):
    """Per-chunk absmax scaling bounds the coordinate error by half a
    quantisation step: |x - dec| <= scale/2 = max|chunk| / 254."""
    dim = 1000
    comp = build("int8", {"chunk": chunk}, dim)
    u = make_update(dim, seed)
    payload, _ = comp.encode(jnp.zeros((dim,), jnp.float32), u)
    dec = np.asarray(comp.decode(payload))
    err = np.abs(np.asarray(u) - dec)
    pad = comp.padded_dim - dim
    bound = np.repeat(
        np.asarray(payload["scales"]), chunk)[:dim] * 0.5 + 1e-7
    assert (err <= bound).all(), float((err - bound).max())
    assert pad >= 0


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 16))
def test_topk_keeps_largest_and_zeroes_rest(seed):
    dim, k = 400, 20
    comp = build("topk", {"k": k}, dim)
    u = make_update(dim, seed)
    payload, residual = comp.encode(jnp.zeros((dim,), jnp.float32), u)
    dec = np.asarray(comp.decode(payload))
    assert (dec != 0).sum() <= k
    # the kept coordinates are shipped exactly, so the residual there
    # is zero and the dropped mass is exactly the dropped coordinates
    idx = np.asarray(payload["indices"])
    np.testing.assert_array_equal(dec[idx], np.asarray(u)[idx])
    np.testing.assert_array_equal(np.asarray(residual)[idx], 0.0)
    kept_min = np.abs(dec[idx]).min()
    dropped = np.delete(np.abs(np.asarray(u)), idx)
    assert dropped.max() <= kept_min + 1e-7


def test_lowrank_recovers_low_rank_signal():
    """A genuinely rank-1 update reconstructs to numerical accuracy."""
    comp = build("lowrank", {"rank": 2}, 900)
    a = jnp.sin(jnp.arange(30, dtype=jnp.float32) * 0.3)
    b = jnp.cos(jnp.arange(30, dtype=jnp.float32) * 0.7)
    u = (a[:, None] * b[None, :]).reshape(-1)
    payload, residual = comp.encode(jnp.zeros((900,), jnp.float32), u)
    np.testing.assert_allclose(np.asarray(comp.decode(payload)),
                               np.asarray(u), atol=1e-5)
    assert float(jnp.abs(residual).max()) < 1e-5


# --------------------------------------------------------- telescoping
@pytest.mark.parametrize("name,kwargs", SPECS)
def test_error_feedback_telescopes(name, kwargs):
    """sum_t decoded_t + residual_T == sum_t update_t: nothing the
    compressor drops is ever lost, it is only deferred."""
    dim, rounds = 601, 6
    comp = build(name, kwargs, dim)
    state = comp.init_state(1)[0]
    total_sent = jnp.zeros((dim,), jnp.float32)
    total_raw = jnp.zeros((dim,), jnp.float32)
    enc = jax.jit(comp.encode)
    for t in range(rounds):
        u = make_update(dim, 100 + t)
        payload, state = enc(state, u)
        total_sent = total_sent + comp.decode(payload)
        total_raw = total_raw + u
    np.testing.assert_allclose(np.asarray(total_sent + state),
                               np.asarray(total_raw), atol=1e-5)


@pytest.mark.parametrize("name,kwargs", SPECS)
def test_no_retrace_across_rounds(name, kwargs):
    """One trace serves every round: payload shapes are static in dim,
    so nothing about the round index leaks into the trace."""
    dim = 520
    comp = build(name, kwargs, dim)
    traces = {"n": 0}

    def enc(state, u):
        traces["n"] += 1
        return comp.encode(state, u)

    enc = jax.jit(enc)
    state = comp.init_state(1)[0]
    for t in range(4):
        _, state = enc(state, make_update(dim, t))
    assert traces["n"] == 1


@pytest.mark.parametrize("name,kwargs", SPECS)
def test_deterministic_and_key_free(name, kwargs):
    """FL001: encoding consumes no PRNG stream — the same input always
    produces the bitwise-same payload, with no key argument anywhere in
    the wire protocol."""
    dim = 333
    comp = build(name, kwargs, dim)
    u = make_update(dim, 9)
    s = jnp.zeros((dim,), jnp.float32)
    p1, r1 = comp.encode(s, u)
    p2, r2 = comp.encode(s, u)
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))


# ------------------------------------------- fused dequant_aggregate
@pytest.mark.parametrize("C,M,chunk,bm", [(4, 1024, 256, 512),
                                          (3, 512, 64, 128),
                                          (1, 256, 256, 256)])
def test_dequant_kernel_matches_ref(C, M, chunk, bm):
    w = jax.random.uniform(jax.random.PRNGKey(0), (C,))
    q = jax.random.randint(jax.random.PRNGKey(1), (C, M), -127, 128,
                           jnp.int8)
    s = jax.random.uniform(jax.random.PRNGKey(2), (C, M // chunk),
                           jnp.float32, 1e-4, 1e-2)
    ref = dequant_aggregate_ref(w, s, q, chunk)
    out = dequant_aggregate_pallas(w, s, q, chunk=chunk, block_m=bm,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-6, rtol=1e-6)


@settings(max_examples=10, deadline=None)
@given(c=st.integers(1, 6), nchunks=st.integers(1, 9),
       seed=st.integers(0, 2 ** 16))
def test_dequant_ops_pallas_route_matches_ref(c, nchunks, seed):
    """The ops padding path (M not a block multiple) stays exact."""
    chunk = 64
    M = nchunks * chunk
    w = jax.random.uniform(jax.random.PRNGKey(seed), (c,))
    q = jax.random.randint(jax.random.PRNGKey(seed + 1), (c, M),
                           -127, 128, jnp.int8)
    s = jax.random.uniform(jax.random.PRNGKey(seed + 2),
                           (c, nchunks), jnp.float32, 1e-4, 1e-2)
    ref = dequant_aggregate_ref(w, s, q, chunk)
    out = dequant_aggregate(w, s, q, chunk=chunk, impl="pallas",
                            block_m=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-6, rtol=1e-6)


def test_int8_aggregate_matches_decode_then_weighted_sum():
    """The fused server step is bitwise the dequantise-then-reduce
    composition it replaces (both accumulate f32 through the same
    einsum contraction)."""
    dim, C = 700, 5
    comp = COMPRESSORS.build("int8", {}, dict(dim=dim))
    states = comp.init_state(C)
    updates = jnp.stack([make_update(dim, 40 + i) for i in range(C)])
    payloads, _ = jax.vmap(comp.encode)(states, updates)
    decoded = jax.vmap(comp.decode)(payloads)
    w = jax.nn.softmax(jnp.arange(C, dtype=jnp.float32))
    fused = comp.aggregate(payloads, decoded, w, impl="naive")
    composed = weighted_aggregate(decoded, w, impl="naive")
    np.testing.assert_array_equal(np.asarray(fused),
                                  np.asarray(composed))
