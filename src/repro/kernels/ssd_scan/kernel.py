"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

Grid ``(Bt, H, num_chunks)`` with the chunk dimension innermost
(sequential on TPU): the running SSM state ``[P, N]`` lives in fp32 VMEM
scratch and is carried across chunk steps. Within a chunk the duality is
exploited — a ``[Q, Q]`` masked-decay attention-like matmul (MXU-friendly)
instead of a length-Q recurrence. B/C state groups (``G <= H``) are mapped
to heads via BlockSpec index maps, never materialised per-head in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, d_ref, x_ref, dt_ref, b_ref, c_ref,
                y_ref, state_out_ref, h_ref, *, chunk: int,
                num_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    h = pl.program_id(1)
    A = a_ref[h]                                   # scalar (negative)
    Dh = d_ref[h]
    xb = x_ref[0, 0].astype(jnp.float32)           # [Q, P]
    dtb = dt_ref[0, 0].astype(jnp.float32)         # [Q, 1]
    Bb = b_ref[0, 0].astype(jnp.float32)           # [Q, N]
    Cb = c_ref[0, 0].astype(jnp.float32)           # [Q, N]

    dA = dtb * A                                   # [Q, 1]
    h0 = h_ref[...]                                # [P, N]

    # Mosaic lowers neither cumsum nor narrow transposes, so the prefix
    # sums and the row views of the [Q, 1] columns are masked
    # reductions over the [Q, Q] tile (VPU work, small beside the
    # matmuls below)
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = i >= j
    dA_row = jnp.sum(jnp.where(i == j, dA, 0.0), axis=0, keepdims=True)
    dt_row = jnp.sum(jnp.where(i == j, dtb, 0.0), axis=0, keepdims=True)
    cum = jnp.sum(jnp.where(lower, dA_row, 0.0), axis=1,
                  keepdims=True)                   # [Q, 1] inclusive
    cum_row = jnp.sum(jnp.where(i <= j, dA, 0.0), axis=0,
                      keepdims=True)               # [1, Q]
    cum_last = jnp.sum(dA, axis=0, keepdims=True)  # [1, 1]

    # intra-chunk (the "duality" quadratic form)
    CB = jax.lax.dot_general(Cb, Bb, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Q, Q]
    rel = jnp.where(lower, cum - cum_row, -1e30)    # mask before exp
    L = jnp.exp(rel) * dt_row                       # [Q, Q]
    y = jax.lax.dot_general(CB * L, xb, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [Q, P]

    # inter-chunk contribution from the carried state
    y += jnp.exp(cum) * jax.lax.dot_general(
        Cb, h0, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)            # [Q, P]

    y_ref[0, 0] = (y + Dh * xb).astype(y_ref.dtype)

    # state update: h <- exp(cum_Q) h + sum_j exp(cum_Q - cum_j) dt_j x_j B_j^T
    w = jnp.exp(cum_last - cum) * dtb                  # [Q, 1]
    h_new = jnp.exp(cum_last) * h0 + jax.lax.dot_general(
        xb * w, Bb, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # [P, N]
    h_ref[...] = h_new

    @pl.when(ci == num_chunks - 1)
    def _emit_state():
        state_out_ref[0, 0] = h_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(x, dt, A, B, C, D, *, chunk: int = 128,
                    interpret: bool = False):
    """Chunked SSD scan. Shapes as in ``ref.ssd_ref``.

    Returns (y [Bt,S,H,P], final_state [Bt,H,P,N] fp32).
    """
    Bt, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    rep = H // G
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk

    xh = x.transpose(0, 2, 1, 3)                     # [Bt, H, S, P]
    dth = dt.transpose(0, 2, 1)[..., None]           # [Bt, H, S, 1]
    Bg = B.transpose(0, 2, 1, 3)                     # [Bt, G, S, N]
    Cg = C.transpose(0, 2, 1, 3)

    grid = (Bt, H, nc)
    kernel = functools.partial(_ssd_kernel, chunk=chunk, num_chunks=nc)

    y, state = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),     # A [H], whole
            pl.BlockSpec(memory_space=pltpu.SMEM),     # D [H], whole
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, h, ci, _rep=rep: (b, h // _rep, ci, 0)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, h, ci, _rep=rep: (b, h // _rep, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, ci: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bt, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((Bt, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(A.astype(jnp.float32), D.astype(jnp.float32), xh, dth, Bg, Cg)
    return y.transpose(0, 2, 1, 3), state
