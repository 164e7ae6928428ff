"""The plain f32 reference of the FedTest round, independent of ``src/``.

``<family>.py`` is the forward pass of a model family the benchmark
runs (``cnn.py``), written from its published description in
straightforward ``jax.numpy``, and found by the configuration's
``family``; ``fedtest.py`` is the round itself
(local SGD, the attack, the K x N accuracy matrix, scoring and the
score-weighted aggregation) over them. A :class:`Precision` says how the
reference computes: the default is float32 at ``highest`` matmul
precision; the controls of ``calibrate.py`` store the weights and compute
in a lower precision.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Callable

import jax
import jax.numpy as jnp


def _identity(x):
    return x


def int8_round_trip(x):
    """Per-tensor absmax int8 quantisation, returned in bfloat16."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-12) / 127.0
    return (jnp.round(xf / scale) * scale).astype(jnp.bfloat16)


@dataclasses.dataclass(frozen=True)
class Precision:
    """How the reference stores weights and computes.

    ``store`` maps a weight leaf to its stored value (after the initial
    cast and after every optimizer step), ``act`` is the activation and
    matmul-input dtype, ``matmul`` the matmul precision.
    """

    name: str = "f32"
    store: Callable = _identity
    act: object = jnp.float32
    matmul: object = jax.lax.Precision.HIGHEST


F32 = Precision()
HIGH = Precision("high", matmul=jax.lax.Precision.HIGH)
BF16 = Precision("bf16", lambda x: x.astype(jnp.bfloat16), jnp.bfloat16,
                 jax.lax.Precision.DEFAULT)
INT8 = Precision("int8", int8_round_trip, jnp.bfloat16,
                 jax.lax.Precision.DEFAULT)


def control(cfg: dict) -> Precision:
    """The nearest precision below the one the configuration states:
    three bf16 passes for float32 at ``highest``, bfloat16 for other
    float32, int8 weights for bfloat16."""
    if cfg["dtype"] == "float32":
        return HIGH if cfg.get("matmul_precision") == "highest" else BF16
    return INT8


def model(family: str):
    """The reference module of a model family (``fedbench/reference/<family>.py``)."""
    return importlib.import_module(f"fedbench.reference.{family}")
