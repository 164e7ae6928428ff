"""Public fused dequantise-aggregate op (int8 payload reduction)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.dequant_aggregate.kernel import dequant_aggregate_pallas
from repro.kernels.dequant_aggregate.ref import dequant_aggregate_ref

# int8 packs 32 rows per (sublane x lane) tile on TPU
_ROW_TILE = 32


def dequant_aggregate(w: jnp.ndarray, scales: jnp.ndarray,
                      q: jnp.ndarray, *, chunk: int = 256,
                      impl: str = "auto", block_m: int = 8192,
                      interpret: bool = False) -> jnp.ndarray:
    """w [C]; scales [C, M/chunk]; q [C, M] int8 -> [M] f32.

    ``M`` must be a whole number of chunks (the Int8 compressor pads at
    encode time); the pallas path additionally pads M up to a block
    multiple with zero codes, which contribute exact +0.0f. A block
    spans either every chunk row or a multiple of ``_ROW_TILE`` rows,
    the alignment the TPU lowering requires of int8 blocks.
    """
    C, M = q.shape
    if M % chunk != 0:
        raise ValueError(f"M={M} must be a multiple of chunk={chunk}")
    if scales.shape != (C, M // chunk):
        raise ValueError(
            f"scales shape {scales.shape} != {(C, M // chunk)}")
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "naive"
    if impl == "naive":
        return dequant_aggregate_ref(w, scales, q, chunk)
    nrows = M // chunk
    rows = max(1, block_m // chunk)
    if rows >= nrows:
        rows = nrows
    else:
        rows = -(-rows // _ROW_TILE) * _ROW_TILE
    pad = (-nrows) % rows
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad * chunk)))
        scales = jnp.pad(scales, ((0, 0), (0, pad)))
    out = dequant_aggregate_pallas(w, scales, q, chunk=chunk,
                                   block_m=rows * chunk,
                                   interpret=interpret)
    return out[:M]
