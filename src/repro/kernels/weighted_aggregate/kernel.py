"""Pallas TPU kernel for the FedTest server's score-weighted model reduction.

The server holds C client models stacked as ``[C, M]`` (flattened params)
and reduces them with score weights. Grid is 1-D over ``M // block_m``;
each step streams a ``[C, block_m]`` tile through VMEM and reduces it on
the VPU with fp32 accumulation. For C ~ 20 clients and bf16 models this is
bandwidth-bound — the tile shape keeps the working set
``C * block_m * itemsize`` well inside VMEM while using full 128-lane rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _wagg_kernel(w_ref, x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)        # [C, block_m]
    w = w_ref[...].astype(jnp.float32)        # [C, 1]
    o_ref[...] = jnp.sum(x * w, axis=0, keepdims=True).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def weighted_aggregate_pallas(x: jnp.ndarray, w: jnp.ndarray, *,
                              block_m: int = 4096,
                              interpret: bool = False) -> jnp.ndarray:
    """x [C, M] (M % block_m == 0); w [C] -> [M]."""
    C, M = x.shape
    block_m = min(block_m, M)
    assert M % block_m == 0, (M, block_m)
    out = pl.pallas_call(
        _wagg_kernel,
        grid=(M // block_m,),
        in_specs=[
            pl.BlockSpec((C, 1), lambda mi: (0, 0)),
            pl.BlockSpec((C, block_m), lambda mi: (0, mi)),
        ],
        out_specs=pl.BlockSpec((1, block_m), lambda mi: (0, mi)),
        out_shape=jax.ShapeDtypeStruct((1, M), x.dtype),
        interpret=interpret,
        name="weighted_aggregate",
    )(w.reshape(C, 1), x)
    return out[0]
