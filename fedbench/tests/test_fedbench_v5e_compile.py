"""The reference's compiled pieces fit one v5e chip at every listed
cell's size: its client-group training step and its chunked cross-test
evaluation, compiled for a described (not attached) v5e. The reference
runs on one chip after the window, so it must fit there."""
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
HBM = 15.75e9           # what the v5e compiler lets one program use


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no libtpu here: nothing to compile
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def cells():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return sorted(w["name"] for w in bench["workloads"])


@pytest.mark.parametrize("name", cells())
def test_reference_fits_one_chip(one_chip, name):
    import jax
    import jax.numpy as jnp
    from fedbench import harness
    from fedbench.reference.fedtest import Round
    from repro.models import build_model

    work = json.loads((ROOT / "fedbench" / "workloads" / f"{name}.json")
                      .read_text())
    cfg = json.loads((ROOT / "fedbench" / "configs" / f"{work['config']}.json")
                     .read_text())
    cell = harness.Cell(name, 1, cfg, work, [], [])
    w = cell.work
    fed = harness.fed_dict(cell)
    group = min(w.get("reference_group", 1), w.get("cohort")
                or fed["num_users"])
    rows = fed["num_testers"] * w["eval_batch"]
    chunk = w.get("reference_eval_chunk", 0) or rows
    rnd = Round(cell.cfg, fed, eval_chunk=chunk, group=group)
    layout = jax.eval_shape(build_model(harness.model_config(cell.cfg)).init,
                            jax.ShapeDtypeStruct((2,), jnp.uint32))

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    sample = tuple(w["sample_shape"])
    label = sample[:-3] if cell.cfg["family"] == "cnn" else sample
    xdt = jnp.float32 if cell.cfg["family"] == "cnn" else jnp.int32
    g = jax.tree_util.tree_map(lambda l: spec(l.shape), layout)
    stacked = jax.tree_util.tree_map(lambda l: spec((group,) + l.shape),
                                     layout)
    steps, batch = fed["local_steps"], fed["batch_size"]
    with jax.default_matmul_precision("highest"):
        train = rnd._train.lower(
            g, spec((group, steps, batch) + sample, xdt),
            spec((group, steps, batch) + label, jnp.int32)).compile()
        evaluate = rnd._correct.lower(
            stacked, spec((chunk,) + sample, xdt),
            spec((chunk,) + label, jnp.int32)).compile()
    for compiled in (train, evaluate):
        m = compiled.memory_analysis()
        assert m.temp_size_in_bytes + m.argument_size_in_bytes < HBM
