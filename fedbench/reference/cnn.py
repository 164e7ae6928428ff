"""The FedTest paper's CNN (Sec. III): three 3x3 'same' convolutions with
ReLU and 2x2 max-pooling, a ReLU hidden layer and a linear classifier.

Weights are the nested dict ``conv<i>/{w,b}`` (``w`` HWIO),
``fc1/{w,b}``, ``fc2/{w,b}``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _maxpool2(x):
    b, h, w, c = x.shape
    x = jnp.pad(x, ((0, 0), (0, h % 2), (0, w % 2), (0, 0)),
                constant_values=-jnp.inf)
    return x.reshape(b, x.shape[1] // 2, 2, x.shape[2] // 2, 2, c).max(
        axis=(2, 4))


def logits(params, cfg, batch, prec):
    x = batch["x"].astype(prec.act)
    i = 0
    while f"conv{i}" in params:
        p = params[f"conv{i}"]
        x = jax.lax.conv_general_dilated(
            x, p["w"].astype(prec.act), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=prec.matmul)
        x = _maxpool2(jax.nn.relu(x + p["b"].astype(prec.act)))
        i += 1
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(jnp.dot(x, params["fc1"]["w"].astype(prec.act),
                            precision=prec.matmul)
                    + params["fc1"]["b"].astype(prec.act))
    out = (jnp.dot(x, params["fc2"]["w"].astype(prec.act),
                   precision=prec.matmul)
           + params["fc2"]["b"].astype(prec.act))
    return out.astype(jnp.float32)
