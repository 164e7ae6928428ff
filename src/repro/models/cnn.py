"""The paper's own model (Sec. III): three conv layers + two fully-connected
layers + softmax, "ideally suited for an image classification problem".

Used for the faithful Fig. 4 / Fig. 5 reproductions (CIFAR-10-like and
MNIST-like synthetic data).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.models.common import dense_init
from repro.utils import key_iter


def init_cnn(cfg, key, dtype=jnp.float32) -> Dict:
    ks = key_iter(key)
    chans = (cfg.image_channels,) + tuple(cfg.cnn_channels)
    p: Dict = {}
    for i in range(len(cfg.cnn_channels)):
        fan_in = 3 * 3 * chans[i]
        p[f"conv{i}"] = {
            "w": (jax.random.truncated_normal(
                next(ks), -2, 2, (3, 3, chans[i], chans[i + 1]), jnp.float32)
                * fan_in ** -0.5).astype(dtype),
            "b": jnp.zeros((chans[i + 1],), dtype),
        }
    # spatial size after len(channels) stride-2 maxpools
    s = cfg.image_size
    for _ in cfg.cnn_channels:
        s = (s + 1) // 2
    flat = s * s * cfg.cnn_channels[-1]
    p["fc1"] = {"w": dense_init(next(ks), (flat, cfg.cnn_hidden), dtype=dtype),
                "b": jnp.zeros((cfg.cnn_hidden,), dtype)}
    p["fc2"] = {"w": dense_init(next(ks), (cfg.cnn_hidden, cfg.num_classes),
                                dtype=dtype),
                "b": jnp.zeros((cfg.num_classes,), dtype)}
    return p


def _maxpool2(x):
    """2x2 stride-2 max-pool of NHWC ``x``, padding an odd H or W with
    -inf. One reduce-window keeps the layout the convolution writes, so
    XLA fuses bias, ReLU and the pool into one pass; its gradient is a
    select-and-scatter, with no count of ties and no divide."""
    _, H, W, _ = x.shape
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1),
                                 ((0, 0), (0, H % 2), (0, W % 2), (0, 0)))


def cnn_forward(p, cfg, images: jnp.ndarray) -> jnp.ndarray:
    """images [B, H, W, C] -> logits [B, num_classes]."""
    x = images
    for i in range(len(cfg.cnn_channels)):
        x = jax.lax.conv_general_dilated(
            x, p[f"conv{i}"]["w"], window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = jax.nn.relu(x + p[f"conv{i}"]["b"])
        x = _maxpool2(x)
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ p["fc1"]["w"] + p["fc1"]["b"])
    return x @ p["fc2"]["w"] + p["fc2"]["b"]
