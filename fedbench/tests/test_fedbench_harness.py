"""The harness: every entry of BENCHMARK.json has its files, new cells
and metrics are found by name, no run happens without a chip, and a run
whose timed path is broken comes out as not correct."""
import importlib
import json
import os
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

from fedbench import harness
from fedbench.tests import tiny_cells

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_cells.make_root(tmp_path_factory.mktemp("tiny"))


def test_every_entry_has_its_files(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert (ROOT / cfg["reference"]).is_file()
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.work["config"] == w["config"]
        importlib.import_module(
            f"fedbench.traffic.{cell.work['generator']}").make
        assert cell.end_to_end and cell.per_layer
        assert {"setup_s", "rounds_per_s"} <= {m["name"]
                                               for m in cell.end_to_end}
    for m in bench["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)


def test_a_new_workload_is_found_by_name(root):
    (root / "fedbench" / "workloads" / "tiny-extra.json").write_text(
        (root / "fedbench" / "workloads" / "tiny-cnn.json").read_text())
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny-extra", "config": "tiny-cnn",
                               "traffic": "tiny-extra", "chips": 1})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell("tiny-extra", root)
    assert cell.cfg["name"] == "tiny-cnn"
    assert [m["name"] for m in cell.end_to_end] == [
        "setup_s", "rounds_per_s"]


def test_a_new_metric_is_found_by_name(tmp_path, monkeypatch):
    import fedbench.metrics
    (tmp_path / "tiny_probe_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    monkeypatch.setattr(fedbench.metrics, "__path__",
                        list(fedbench.metrics.__path__) + [str(tmp_path)])
    assert harness.metric_reader("tiny_probe_metric")(None) == 42.0


def _run_py(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "fedbench/run.py", "--workload", "cnn-paper",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_result_without_a_chip():
    p = _run_py(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == "", p.stderr[-2000:]
    assert "no TPU" in p.stderr


def test_no_result_from_the_benchmark_files_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "fedbench", tmp_path / "fedbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


def run(root, name="tiny-cnn", trace=False):
    with jax.default_matmul_precision("highest"):
        return harness.run_cell(name, 5, 0.3, trace,
                                t_start=time.perf_counter(), root=root,
                                require_chip=False, log=lambda m: None)


def test_a_sound_run_is_correct(root):
    line = run(root)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"setup_s", "rounds_per_s"}
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())


def test_a_traced_run_reads_its_per_layer_metrics(root):
    line = run(root, trace=True)
    assert line["correct"]
    assert set(line["device"]) >= {"busy_s", "window_s"}
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    # the CPU has no device planes: nothing to read, so no metric
    assert line["metrics"] == {}


def test_a_step_that_leaves_the_model_unchanged_is_caught(root,
                                                          monkeypatch):
    from repro.core.engine.program import RoundProgram
    sound = RoundProgram.run

    def unchanged(self, backend, global_params, *a, **kw):
        _, scores, comp, metrics = sound(self, backend, global_params,
                                         *a, **kw)
        return global_params, scores, comp, metrics

    monkeypatch.setattr(RoundProgram, "run", unchanged)
    line = run(root)
    assert not line["correct"]
    assert line["checks"]["updateN"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out_is_caught(root, monkeypatch):
    from repro.core.engine.program import RoundProgram
    sound = RoundProgram.local_train

    def half(self, params, bx, by):
        b = bx.shape[1] // 2
        return sound(self, params, bx[:, :b], by[:, :b])

    monkeypatch.setattr(RoundProgram, "local_train", half)
    line = run(root)
    assert not line["correct"]


def test_an_altered_tester_report_is_caught(root, monkeypatch):
    from repro.core.engine.backends import LocalBackend
    sound = LocalBackend.cross_test

    def altered(self, *a, **kw):
        acc, cache = sound(self, *a, **kw)
        lies = jax.random.uniform(jax.random.PRNGKey(0), acc.shape[1:])
        return acc.at[0].set(lies), cache

    monkeypatch.setattr(LocalBackend, "cross_test", altered)
    line = run(root)
    assert not line["correct"]
    assert line["checks"]["scores"]["value"] > 1e-2
