"""The cohort cell's exchange between chips: on four CPU devices the
sharded cohort round is correct, and with the reduction across devices
left out it is not. Runs in a child process, which alone may set the
host device count."""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

CHILD = r"""
import json, sys, time
sys.path[:0] = [{root!r}, {src!r}]
import jax
from fedbench import harness
from fedbench.tests import tiny_cells
from repro.core.engine.population import PopulationBackend

root = tiny_cells.make_root(__import__("pathlib").Path({tmp!r}), chips=4)
out = {{}}
def run():
    with jax.default_matmul_precision("highest"):
        return harness.run_cell("tiny-cohort", 9, 0.3, False,
                                t_start=time.perf_counter(), root=root,
                                require_chip=False, log=lambda m: None)
out["sound"] = run()
def no_psum(self, fn, *args):
    if self.mesh is None:
        return fn(*args)
    from jax.sharding import PartitionSpec as P
    return jax.shard_map(fn, mesh=self.mesh,
                         in_specs=(P(self.axis),) * len(args),
                         out_specs=P(), check_vma=False)(*args)
PopulationBackend._cohort_sum = no_psum
out["no_exchange"] = run()
print(json.dumps({{k: v["correct"] for k, v in out.items()}}))
"""


def test_exchange_between_devices_left_out_is_caught(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.format(root=str(ROOT), src=str(ROOT / "src"),
                        tmp=str(tmp_path))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got == {"sound": True, "no_exchange": False}
