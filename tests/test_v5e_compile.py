"""The kernels and one client's training step compile for a TPU v5e.

No chip is needed: the TPU compiler compiles for a described
``v5e:2x2`` topology, so what the chip's compiler would refuse (block
shapes off the tiling, Mosaic lowerings that do not exist, a kernel
differentiated without a backward) fails here. The topology is
described inside a module fixture, never at import, and the file skips
where it cannot be described.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import FedConfig, TrainConfig
from repro.configs import get_config
from repro.core.engine.program import RoundProgram
from repro.models import build_model


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_cases():
    """name -> (fn, [(shape, dtype)]) at real widths."""
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.dequant_aggregate.ops import dequant_aggregate
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.robust_combine.ops import robust_combine
    from repro.kernels.ssd_scan.ops import ssd_scan
    from repro.kernels.weighted_aggregate.ops import weighted_aggregate

    C, M = 20, 2 ** 20
    f32, bf16 = jnp.float32, jnp.bfloat16
    # qwen2-0.5b heads: 14 query, 2 kv, head_dim 64
    S, hq, hkv, d = 2048, 14, 2, 64
    # mamba2-2.7b heads: H = 2 * 2560 / 64 = 80, P = 64, N = 128
    H, P, N = 80, 64, 128
    return {
        "weighted_aggregate": (
            lambda x, w: weighted_aggregate(x, w, impl="pallas"),
            [((C, M), f32), ((C,), f32)]),
        "robust_combine": (
            lambda x: robust_combine(x, impl="pallas"),
            [((C, M), f32)]),
        "dequant_aggregate": (
            lambda w, s, q: dequant_aggregate(w, s, q, chunk=256,
                                              impl="pallas"),
            [((C,), f32), ((C, M // 256), f32), ((C, M), jnp.int8)]),
        "flash_attention": (
            lambda q, k, v: flash_attention(q, k, v, impl="pallas"),
            [((1, S, hq, d), bf16), ((1, S, hkv, d), bf16),
             ((1, S, hkv, d), bf16)]),
        "decode_attention": (
            lambda q, k, v, n: decode_attention(q, k, v, n, impl="pallas"),
            [((8, hq, d), bf16), ((8, S, hkv, d), bf16),
             ((8, S, hkv, d), bf16), ((8,), jnp.int32)]),
        "ssd_scan": (
            lambda x, dt, a, b, c, dd: ssd_scan(x, dt, a, b, c, dd,
                                                chunk=256, impl="pallas"),
            [((1, S, H, P), bf16), ((1, S, H), f32), ((H,), f32),
             ((1, S, 1, N), bf16), ((1, S, 1, N), bf16), ((H,), f32)]),
    }


@pytest.mark.parametrize("name", ["weighted_aggregate", "robust_combine",
                                  "dequant_aggregate", "flash_attention",
                                  "decode_attention", "ssd_scan"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, specs = _kernel_cases()[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen2_local_training_step_compiles_for_v5e(one_chip,
                                                    monkeypatch):
    """One client's local phase at qwen2-0.5b widths, depth cut to 2
    layers, with ``auto`` kernels resolved as on a TPU host: training
    must take the XLA attention (no Pallas kernel has a backward)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("qwen2-0.5b").replace(num_layers=2)
    model = build_model(cfg)
    fed = FedConfig(num_users=1, num_testers=1, local_steps=2)
    tc = TrainConfig(optimizer="sgd", lr=0.05, schedule="constant",
                     batch_size=4, grad_clip=0.0, remat=False)
    program = RoundProgram(model, fed, tc)

    def spec(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = spec(jax.eval_shape(model.init,
                                 jax.ShapeDtypeStruct((2,), jnp.uint32)))
    tokens = jax.ShapeDtypeStruct((fed.local_steps, tc.batch_size, 64),
                                  jnp.int32, sharding=one_chip)
    compiled = jax.jit(program.local_train).lower(params, tokens,
                                                  tokens).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    # cross-testing on the same host does reach the Pallas kernel
    x = jax.ShapeDtypeStruct((tc.batch_size, 64), jnp.int32,
                             sharding=one_chip)
    evaluated = jax.jit(program.eval_fn).lower(params, x, x).compile()
    assert "tpu_custom_call" in evaluated.as_text()
