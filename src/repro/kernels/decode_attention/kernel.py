"""Pallas TPU flash-decoding kernel.

One new token per sequence attends a long KV cache. The cache is streamed
through VMEM in ``block_k`` tiles along the sequential innermost grid
dimension, with the online-softmax state in scratch. Emits (out, lse) so a
sequence-sharded cache can be combined with an LSE-weighted merge — the
TPU-native analogue of GPU flash-decoding split-K.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                   acc_ref, m_ref, l_ref, *,
                   scale: float, block_k: int, num_k_blocks: int,
                   window: Optional[int]):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[pl.program_id(0)]
    k_start = ki * block_k
    lo = length - window if window is not None else 0
    run = jnp.logical_and(k_start < length, k_start + block_k > lo)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)         # [rep, D]
        k = k_ref[0, 0].astype(jnp.float32)         # [block_k, D] (kv head g)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [Hq, block_k]
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = kpos < length
        if window is not None:
            valid &= kpos >= length - window
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[...] + jnp.log(l)).astype(lse_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_k", "window", "interpret"))
def decode_attention_pallas(q, k, v, lengths, *,
                            scale: Optional[float] = None,
                            block_k: int = 256,
                            window: Optional[int] = None,
                            interpret: bool = False):
    """q [B,Hq,D]; k/v [B,T,Hkv,D]; lengths [B] -> (out [B,Hq,D], lse [B,Hq]).

    GQA grid: (B, Hkv, T // block_k); each step handles one kv head's whole
    query-head group (rep = Hq // Hkv rows of q). ``lengths`` sits whole
    in SMEM and the lse is emitted as ``[..., rep, 1]`` columns, so every
    block's trailing dimensions are whole array dimensions.
    """
    B, Hq, D = q.shape
    _, T, Hkv, _ = k.shape
    assert Hq % Hkv == 0
    rep = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    block_k = min(block_k, T)
    assert T % block_k == 0
    num_k_blocks = T // block_k

    qg = q.reshape(B, Hkv, rep, D)                  # group-major query heads
    kh = k.transpose(0, 2, 1, 3)                    # [B, Hkv, T, D]
    vh = v.transpose(0, 2, 1, 3)

    grid = (B, Hkv, num_k_blocks)
    kernel = functools.partial(
        _decode_kernel, scale=scale, block_k=block_k,
        num_k_blocks=num_k_blocks, window=window)

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, rep, D), lambda b, g, ki: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, g, ki: (b, g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, g, ki: (b, g, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, rep, D), lambda b, g, ki: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, rep, 1), lambda b, g, ki: (b, g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, rep, D), q.dtype),
            jax.ShapeDtypeStruct((B, Hkv, rep, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((rep, D), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
        ],
        interpret=interpret,
        name="decode_attention",
    )(lengths.astype(jnp.int32), qg, kh, vh)
    return out.reshape(B, Hq, D), lse.reshape(B, Hq)
