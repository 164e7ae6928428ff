"""Pallas TPU flash-attention kernel (GQA, causal / sliding-window).

Target layout inside the kernel: heads-major ``[B, H, S, D]`` so each grid
step streams contiguous (block_q x D) / (block_k x D) tiles through VMEM.

Grid: ``(B, Hq, S // block_q, T // block_k)`` — the KV-block dimension is
innermost, i.e. sequential on TPU, so the online-softmax running state
(m, l, acc) lives in VMEM scratch and is revisited across KV steps.
GQA is expressed in the K/V BlockSpec index maps (``h // group``) so grouped
KV heads are never materialised ``rep`` times in HBM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool,
                  sliding_window: Optional[int],
                  block_q: int, block_k: int,
                  num_k_blocks: int, q_offset: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q + q_offset
    k_start = ki * block_k

    # Skip blocks that are fully masked out (above the causal diagonal or
    # entirely left of the sliding window).
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1
    if sliding_window is not None:
        run = jnp.logical_and(
            run, k_start + block_k - 1 > q_start - sliding_window)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)        # [block_q, D]
        k = k_ref[0, 0].astype(jnp.float32)        # [block_k, D]
        v = v_ref[0, 0].astype(jnp.float32)        # [block_k, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones(s.shape, dtype=bool)
        if causal:
            mask &= kpos <= qpos
        if sliding_window is not None:
            mask &= kpos > qpos - sliding_window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                         # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                      # [bq, bk]
        corr = jnp.exp(m_prev - m_new)              # [bq, 1]
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "sliding_window", "scale", "block_q",
                     "block_k", "q_offset", "interpret"))
def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           causal: bool = True,
                           sliding_window: Optional[int] = None,
                           scale: Optional[float] = None,
                           block_q: int = 128,
                           block_k: int = 128,
                           q_offset: int = 0,
                           interpret: bool = False) -> jnp.ndarray:
    """q [B,S,Hq,D], k/v [B,T,Hkv,D] -> [B,S,Hq,D]."""
    B, S, Hq, D = q.shape
    _, T, Hkv, _ = k.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    assert S % block_q == 0 and T % block_k == 0, (S, T, block_q, block_k)
    num_k_blocks = T // block_k

    qh = q.transpose(0, 2, 1, 3)     # [B, Hq, S, D]
    kh = k.transpose(0, 2, 1, 3)     # [B, Hkv, T, D]
    vh = v.transpose(0, 2, 1, 3)

    grid = (B, Hq, S // block_q, num_k_blocks)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal,
        sliding_window=sliding_window, block_q=block_q, block_k=block_k,
        num_k_blocks=num_k_blocks, q_offset=q_offset)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(qh, kh, vh)
    return out.transpose(0, 2, 1, 3)
