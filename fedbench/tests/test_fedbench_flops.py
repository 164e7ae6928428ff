"""Model FLOPs, parameter counts and the peaks table, pinned to hand
counts."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from fedbench import flops, peaks
from fedbench.harness import model_config

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_cnn_hand_counts():
    c = cfg("fedtest-cnn")
    # conv 1.77 + 9.44 + 4.72 MFLOP, FC 0.26 MFLOP
    assert flops.cnn_forward_flops(c) == (
        2 * 32 * 32 * 9 * 3 * 32 + 2 * 16 * 16 * 9 * 32 * 64
        + 2 * 8 * 8 * 9 * 64 * 64 + 2 * 1024 * 128 + 2 * 128 * 10)
    assert flops.cnn_forward_flops(c) == pytest.approx(16.2e6, rel=1e-3)
    assert flops.cnn_params(c) == 188_810


# Qwen2-0.5B (huggingface.co/Qwen/Qwen2-0.5B, config.json): the dense
# family's counts, pinned for the cell that will list it
QWEN2_0P5B = {"name": "qwen2-0.5b", "family": "dense", "num_layers": 24,
              "d_model": 896, "num_heads": 14, "num_kv_heads": 2,
              "head_dim": 64, "d_ff": 4864, "vocab_size": 151936,
              "qkv_bias": True, "rope_theta": 1e6, "norm_eps": 1e-6,
              "max_position": 131072, "tie_embeddings": True,
              "dtype": "bfloat16"}


def test_qwen2_hand_counts():
    c = QWEN2_0P5B
    assert flops.dense_params(c) == 494_032_768
    # 2 x non-embedding matmul weights + the tied head, per token, and
    # causal attention over 64 tokens
    per_seq = flops.dense_forward_flops(c, 64)
    assert per_seq == (2 * 24 * 14_909_440 + 2 * 896 * 151_936) * 64 \
        + 24 * 4 * 14 * 64 * 64 * 65 // 2


@pytest.mark.parametrize("c", [cfg("fedtest-cnn"), QWEN2_0P5B],
                         ids=["fedtest-cnn", "qwen2-0.5b"])
def test_param_count_matches_the_program_layout(c):
    from repro.models import build_model
    abstract = jax.eval_shape(build_model(model_config(c)).init,
                              jax.ShapeDtypeStruct((2,), jnp.uint32))
    assert sum(flops.leaf_sizes(abstract)) == flops.param_count(c)


def test_round_flops_of_the_paper_cell():
    c = cfg("fedtest-cnn")
    f = flops.cnn_forward_flops(c)
    got = flops.round_model_flops(c, (32, 32, 3), trained_clients=20,
                                  local_steps=10, batch=32, testers=5,
                                  tested_models=20, eval_rows=256)
    assert got == 3 * f * 20 * 10 * 32 + f * 5 * 20 * 256
    assert got == pytest.approx(311e9 + 415e9, rel=2e-3)


def test_peaks_are_keyed_by_device_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks("cpu")
