"""Pallas TPU kernel: fused int8 dequantise + score-weighted reduction.

The server holds C compressed client payloads — ``q [C, M]`` int8 codes
and ``scales [C, M / chunk]`` f32 per-chunk absmax scales — and needs
``sum_c w_c * dequant(q_c)``. Doing that in two XLA ops would round-trip
the dequantised f32 ``[C, M]`` stack through HBM (4x the int8 bytes);
this kernel fuses both in one VMEM pass so the reduction streams the
*compressed* representation, staying bandwidth-bound like
``weighted_aggregate`` but at the int8 byte count (DESIGN.md §12).

The codes are viewed as ``[C, M / chunk, chunk]`` and the scales as
``[C, M / chunk, 1]`` (free row-major reshapes), so the per-chunk scale
broadcasts along the lane axis and every block's last two dimensions
are tile-aligned for any ``chunk``: ``chunk`` is the whole lane extent
and the chunk-row extent is a multiple of 32 (the int8 sublane tiling)
or the whole array. Grid is 1-D over chunk-row blocks; each step
streams ``[C, rows, chunk]`` int8 codes plus their ``[C, rows, 1]``
scales through VMEM, dequantises on the VPU, and reduces with fp32
accumulation. The dequantisation is bitwise-identical to
``Int8.decode`` (same multiply), so the pallas and naive paths agree
exactly wherever the platform's f32 arithmetic does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _dqagg_kernel(w_ref, s_ref, q_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)            # [C, rows, chunk]
    s = s_ref[...].astype(jnp.float32)            # [C, rows, 1]
    w = w_ref[...].astype(jnp.float32)            # [C, 1, 1]
    o_ref[...] = jnp.sum((q * s) * w, axis=0)     # [rows, chunk]


@functools.partial(jax.jit,
                   static_argnames=("chunk", "block_m", "interpret"))
def dequant_aggregate_pallas(w: jnp.ndarray, scales: jnp.ndarray,
                             q: jnp.ndarray, *, chunk: int,
                             block_m: int = 8192,
                             interpret: bool = False) -> jnp.ndarray:
    """w [C]; scales [C, M/chunk]; q [C, M] int8 -> [M] f32.

    ``M % block_m == 0`` and ``block_m % chunk == 0`` so every grid step
    sees whole chunks (the ops wrapper pads and aligns the block).
    """
    C, M = q.shape
    block_m = min(block_m, M)
    assert block_m % chunk == 0, (block_m, chunk)
    rows, nrows = block_m // chunk, M // chunk
    assert nrows % rows == 0, (M, block_m)
    out = pl.pallas_call(
        _dqagg_kernel,
        grid=(nrows // rows,),
        in_specs=[
            pl.BlockSpec((C, 1, 1), lambda mi: (0, 0, 0)),
            pl.BlockSpec((C, rows, 1), lambda mi: (0, mi, 0)),
            pl.BlockSpec((C, rows, chunk), lambda mi: (0, mi, 0)),
        ],
        out_specs=pl.BlockSpec((rows, chunk), lambda mi: (mi, 0)),
        out_shape=jax.ShapeDtypeStruct((nrows, chunk), jnp.float32),
        interpret=interpret,
        name="dequant_aggregate",
    )(w.reshape(C, 1, 1), scales.reshape(C, nrows, 1),
      q.reshape(C, nrows, chunk))
    return out.reshape(M)
