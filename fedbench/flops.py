"""Model FLOPs and parameter counts, from shapes alone.

Model FLOPs count the work the round needs: 2 x multiply-adds of every
matmul and convolution of the forward pass, 3 x forward for a training
sample (forward plus the two backward products), causal attention over
the tokens a query may see. Recomputation is never counted.
"""
from __future__ import annotations

import math


def _cnn_spatial(cfg) -> list:
    s, out = cfg["image_size"], []
    for _ in cfg["cnn_channels"]:
        out.append(s)
        s = (s + 1) // 2
    return out + [s]


def cnn_forward_flops(cfg) -> int:
    """Per image."""
    chans = [cfg["image_channels"]] + list(cfg["cnn_channels"])
    sizes = _cnn_spatial(cfg)
    total = sum(2 * sizes[i] ** 2 * 9 * chans[i] * chans[i + 1]
                for i in range(len(cfg["cnn_channels"])))
    flat = sizes[-1] ** 2 * chans[-1]
    return total + 2 * flat * cfg["cnn_hidden"] \
        + 2 * cfg["cnn_hidden"] * cfg["num_classes"]


def cnn_params(cfg) -> int:
    chans = [cfg["image_channels"]] + list(cfg["cnn_channels"])
    n = sum(9 * chans[i] * chans[i + 1] + chans[i + 1]
            for i in range(len(cfg["cnn_channels"])))
    flat = _cnn_spatial(cfg)[-1] ** 2 * chans[-1]
    return n + flat * cfg["cnn_hidden"] + cfg["cnn_hidden"] \
        + cfg["cnn_hidden"] * cfg["num_classes"] + cfg["num_classes"]


def _dense_layer_matmul_params(cfg) -> int:
    d, hq, hkv, dh, f = (cfg["d_model"], cfg["num_heads"],
                         cfg["num_kv_heads"], cfg["head_dim"], cfg["d_ff"])
    return d * (hq + 2 * hkv) * dh + hq * dh * d + 3 * d * f


def dense_params(cfg) -> int:
    d, hq, hkv, dh = (cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"],
                      cfg["head_dim"])
    bias = (hq + 2 * hkv) * dh if cfg.get("qkv_bias") else 0
    layer = _dense_layer_matmul_params(cfg) + bias + 2 * d
    head = 0 if cfg.get("tie_embeddings") else d * cfg["vocab_size"]
    return cfg["num_layers"] * layer + cfg["vocab_size"] * d + d + head


def dense_forward_flops(cfg, seq_len: int) -> int:
    """Per sequence of ``seq_len`` tokens."""
    per_token = (2 * cfg["num_layers"] * _dense_layer_matmul_params(cfg)
                 + 2 * cfg["d_model"] * cfg["vocab_size"])
    # scores and values: 2 matmuls of head_dim per visible key, causal
    attn = (cfg["num_layers"] * 4 * cfg["num_heads"] * cfg["head_dim"]
            * seq_len * (seq_len + 1) // 2)
    return per_token * seq_len + attn


def forward_flops(cfg, sample_shape) -> int:
    """Forward FLOPs of one sample (an image, or a token sequence)."""
    if cfg["family"] == "cnn":
        return cnn_forward_flops(cfg)
    return dense_forward_flops(cfg, sample_shape[-1])


def param_count(cfg) -> int:
    return cnn_params(cfg) if cfg["family"] == "cnn" else dense_params(cfg)


def round_model_flops(cfg, sample_shape, *, trained_clients: int,
                      local_steps: int, batch: int, testers: int,
                      tested_models: int, eval_rows: int) -> int:
    """Useful model FLOPs of one FedTest round: training of the
    participants, and each tester's pass over each tested model."""
    f = forward_flops(cfg, sample_shape)
    return (3 * f * trained_clients * local_steps * batch
            + f * testers * tested_models * eval_rows)


def leaf_sizes(abstract_params) -> list:
    import jax
    return [math.prod(l.shape) for l in jax.tree_util.tree_leaves(
        abstract_params)]
