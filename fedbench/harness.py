"""One run of one cell: set-up, the measured window, the check, the line.

The cell's pieces are found by name: its entry in ``BENCHMARK.json``,
its workload file ``fedbench/workloads/<cell>.json`` (federation,
traffic generator and parameters, training, the check's rounds and
limits), its configuration file (``configs[].file``) and, in a traced
run, one reader per per-layer metric (``fedbench/metrics/<metric>.py``).

Set-up builds the trainer once, makes the weights from the seed on the
device, and drives the trainer's public round entry point
(``FederatedTrainer.run_round`` or ``PopulationTrainer.run_round``)
through the check's first rounds, which compile the round. The window
then calls that same entry point for ``--seconds``, each round waiting
for its scalars. After the window the global evaluation runs once for
the record, the program's state is freed, and the plain reference
(``fedbench/reference``) replays the first rounds for the check.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------------ spec
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict               # the configuration file
    work: dict              # the workload file
    end_to_end: List[dict]
    per_layer: List[dict]


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    work = json.loads((root / "fedbench" / "workloads" / f"{name}.json")
                      .read_text())
    return Cell(name=name, chips=entry["chips"],
                cfg=json.loads((root / conf["file"]).read_text()),
                work=work,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)])


def seeds(seed: int) -> Dict[str, int]:
    """Independent 32-bit seeds for the weights, the run key and the
    data, from a seed of any size."""
    w, r, d = np.random.SeedSequence(seed).generate_state(3)
    return {"weights": int(w), "run": int(r), "data": int(d) >> 1}


# --------------------------------------------------------------- program
def fed_dict(cell: Cell) -> dict:
    """The federation as the reference reads it."""
    f = dict(cell.work["federation"])
    f.update(cell.work["train"])
    n, m = f["num_users"], f["num_malicious"]
    f["malicious"] = list(range(n - m, n))      # the 'last' placement
    f.setdefault("participation", 1.0)
    f["eval_batch"] = cell.work["eval_batch"]
    if cell.work["engine"] == "population":
        f.update(cohort=cell.work["cohort"], testers_from_cohort=True)
    return f


def model_config(cfg: dict):
    from repro.config import ModelConfig
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: (tuple(v) if isinstance(v, list) else v)
          for k, v in cfg.items() if k in fields}
    return ModelConfig(**kw)


class Program:
    """The system under test for one cell, built once."""

    def __init__(self, cell: Cell, devices):
        import jax
        from repro.config import FedConfig, TrainConfig
        from repro.models import build_model

        self.cell, self.devices = cell, devices
        w = cell.work
        self.model = build_model(model_config(cell.cfg))
        fed = dict(w["federation"])
        self.population = w["engine"] == "population"
        if self.population:
            fed["cohort"] = w["cohort"]
        self.fed = FedConfig(rounds=1 << 30, **fed)
        t = w["train"]
        self.tc = TrainConfig(optimizer=t["optimizer"], lr=t["lr"],
                              schedule="constant",
                              batch_size=t["batch_size"], grad_clip=0.0,
                              remat=False)
        self.mesh = None
        if self.population:
            from jax.sharding import Mesh
            from repro.core import PopulationTrainer
            if len(devices) > 1:
                self.mesh = Mesh(np.asarray(devices), ("clients",))
            self.trainer = PopulationTrainer(
                self.model, self.fed, self.tc, eval_batch=w["eval_batch"],
                cohort=w["cohort"], crosstest_block=w["crosstest_block"],
                mesh=self.mesh, testers_from_cohort=True)
        else:
            from repro.core import FederatedTrainer
            self.trainer = FederatedTrainer(self.model, self.fed, self.tc,
                                            eval_batch=w["eval_batch"])
        self.abstract = jax.eval_shape(
            self.model.init, jax.ShapeDtypeStruct((2,), jax.numpy.uint32))
        self._norms = jax.jit(lambda a, b: [
            jax.numpy.linalg.norm((x.astype("float32")
                                   - y.astype("float32")).ravel())
            for x, y in zip(jax.tree_util.tree_leaves(a),
                            jax.tree_util.tree_leaves(b))])

    def data(self, traffic: dict):
        import jax.numpy as jnp
        if self.population:
            from repro.data.population import make_synthetic_population
            p = dict(traffic["population"])
            return make_synthetic_population(
                p.pop("num_clients"), seed=p.pop("seed"), **p)
        from repro.data.pipeline import ClientData, FederatedDataset
        a = {k: jnp.asarray(v) for k, v in traffic.items()}
        return FederatedDataset(
            train=ClientData(a["train_x"], a["train_y"], a["train_counts"]),
            test=ClientData(a["test_x"], a["test_y"], a["test_counts"]),
            global_x=a["global_x"], global_y=a["global_y"],
            server_x=a["server_x"], server_y=a["server_y"])

    def state(self, weights, run_key):
        import jax
        import jax.numpy as jnp
        from repro.core.engine.driver import RoundState
        from repro.core.scoring import init_scores
        st = RoundState(global_params=weights,
                        scores=init_scores(self.fed.num_users),
                        round_idx=jnp.zeros((), jnp.int32), key=run_key)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            st = jax.device_put(st, NamedSharding(self.mesh, P()))
        return st

    def norms(self, a, b) -> List[float]:
        return [float(x) for x in self._norms(a, b)]


def program_precision(cell: Cell):
    """The matmul precision the configuration states for the program
    (``matmul_precision``; JAX's default where it states none)."""
    import contextlib
    import jax
    p = cell.cfg.get("matmul_precision")
    return jax.default_matmul_precision(p) if p else \
        contextlib.nullcontext()


def weights_for(program: Program, seed: int):
    import jax
    from fedbench import weights
    return weights.make(program.abstract, jax.random.PRNGKey(seed))


def record(metrics: dict) -> dict:
    import jax
    m = jax.device_get({k: metrics[k] for k in
                        ("local_loss", "weights", "scores",
                         "malicious_weight")})
    return {k: (float(v) if np.ndim(v) == 0 else np.asarray(v, np.float64))
            for k, v in m.items()}


def drive_first_rounds(program: Program, state, data, rounds: int):
    """The check's first rounds through the window's own entry point.
    Returns the state after them and the readings the check compares."""
    g0 = state.global_params
    out = {"local_loss": [], "weights": [], "scores": [],
           "malicious_weight": []}
    for r in range(rounds):
        state, m = program.trainer.run_round(state, data)
        for k, v in record(m).items():
            out[k].append(v)
        if r == 0:
            out["update1"] = program.norms(state.global_params, g0)
    out["updateN"] = program.norms(state.global_params, g0)
    return state, out


def reference_rounds(cell: Cell, layout, seeds_: dict,
                     traffic: dict, rounds: int, *, prec=None,
                     fault: Optional[str] = None,
                     exchange_parts: int = 1) -> dict:
    """The plain reference over the same first rounds, from the same
    weights (``layout``: the program's leaf shapes and dtypes)."""
    import jax
    import jax.numpy as jnp
    from fedbench import weights
    from fedbench.reference import F32
    from fedbench.reference.fedtest import Round, data_source

    prec = prec or F32
    rnd = Round(cell.cfg, fed_dict(cell), prec=prec, fault=fault,
                eval_chunk=cell.work.get("reference_eval_chunk", 0),
                group=cell.work.get("reference_group", 1),
                exchange_parts=exchange_parts)
    g0 = jax.tree_util.tree_map(
        lambda l: prec.store(l.astype(jnp.float32)),
        weights.make(layout, jax.random.PRNGKey(seeds_["weights"])))
    run_key = jax.random.PRNGKey(seeds_["run"])
    data = data_source(traffic)
    n = data.num_clients
    sc = {"scores": np.zeros((n,)), "rounds_seen": 0}
    norm = jax.jit(lambda a, b: jnp.linalg.norm(
        (a.astype(jnp.float32) - b.astype(jnp.float32)).ravel()))

    def norms(a, b):
        return [float(norm(x, y)) for x, y in zip(
            jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))]

    out = {"local_loss": [], "weights": [], "scores": [],
           "malicious_weight": []}
    g = g0
    for r in range(rounds):
        g, sc, m = rnd.run(g, sc, r, run_key, data)
        for k in ("local_loss", "weights", "scores", "malicious_weight"):
            out[k].append(m[k])
        if r == 0:
            out["update1"] = norms(g, g0)
    out["updateN"] = norms(g, g0)
    return out


# ------------------------------------------------------------------ chips
def find_chips(need: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < need:
        raise NoChip(f"the cell needs {need} chips, JAX found {len(devs)}")
    return devs[:need]


def _annotate(name: str, on: bool):
    import contextlib
    import jax
    return jax.profiler.TraceAnnotation(name) if on else \
        contextlib.nullcontext()


# -------------------------------------------------------------------- run
def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: Path = ROOT,
             require_chip: bool = True, log=None) -> dict:
    """One run; returns the result line as a dict (``checks`` last)."""
    import jax

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    cell = load_cell(name, root)
    with program_precision(cell):
        return _run(cell, seed, seconds, trace, t_start, require_chip, log)


def _run(cell: Cell, seed: int, seconds: float, trace: bool,
         t_start: float, require_chip: bool, log) -> dict:
    import jax
    from fedbench import traffic as traffic_mod

    name = cell.name
    marks = [("start", t_start)]

    def mark(what):
        marks.append((what, time.perf_counter()))

    devices = find_chips(cell.chips) if require_chip else \
        jax.devices()[:cell.chips]
    mark("chips")
    s = seeds(seed)
    work = cell.work
    program = Program(cell, devices)
    mark("build")
    traffic = traffic_mod.make(work["generator"], work["traffic"],
                               s["data"])
    data = program.data(traffic)
    mark("data")
    state = program.state(weights_for(program, s["weights"]),
                          jax.random.PRNGKey(s["run"]))
    jax.block_until_ready(state)
    mark("weights")
    rounds_checked = work["check"]["rounds"]
    state, prog_readings = drive_first_rounds(program, state, data,
                                              rounds_checked)
    jax.block_until_ready(state)
    mark("first rounds")
    traces_before = program.trainer.num_traces
    setup_s = time.perf_counter() - t_start
    log(f"[{name}] set-up {setup_s:.2f} s: " + ", ".join(
        f"{w} {t - marks[i][1]:.2f} s" for i, (w, t) in
        enumerate(marks[1:])) + f"; round traced {traces_before} time(s)")

    times, participants, nonfinite = [], [], 0
    tdir = tempfile.mkdtemp(prefix="fedbench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    n = program.fed.num_users
    t0 = time.perf_counter()
    with _annotate("fedbench.window", trace):
        while True:
            r0 = time.perf_counter()
            with _annotate("fedbench.dispatch", trace):
                state, m = program.trainer.run_round(state, data)
            with _annotate("fedbench.wait", trace):
                loss, part = jax.device_get(
                    (m["local_loss"], m["participation_rate"]))
            r1 = time.perf_counter()
            times.append(r1 - r0)
            participants.append(float(part) * n)
            nonfinite += int(not math.isfinite(float(loss)))
            if r1 - t0 >= seconds:
                break
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    retraces = program.trainer.num_traces - traces_before
    failed = nonfinite + retraces
    attempted = len(times)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

    with _annotate("fedbench.global_eval", trace):
        acc = program.trainer.global_accuracy(state, data)
    log(f"[{name}] window {window_s:.3f} s, {attempted} rounds, "
        f"{retraces} retrace(s); global accuracy after it {acc:.4f} "
        "(record only)")

    metrics: Dict[str, dict] = {}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    breakdown = None
    if trace:
        from fedbench import trace as trace_mod
        summary = trace_mod.reduce(trace_mod.load(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        ctx = MetricContext(cell=cell, summary=summary,
                            participants=participants,
                            abstract=program.abstract,
                            kind=devices[0].device_kind, chips=len(devices))
        for spec in cell.per_layer:
            value = metric_reader(spec["name"])(ctx)
            if value is not None:
                metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
        breakdown = {"device_ops": [list(x) for x in summary.top_ops(10)],
                     "idle_gaps": [list(x) for x in summary.idle_gaps[:10]]}
    else:
        e2e = {"setup_s": setup_s, "rounds_per_s": attempted / window_s}
        for spec in cell.end_to_end:
            metrics[spec["name"]] = {"value": e2e[spec["name"]],
                                     "unit": spec["unit"]}

    abstract = program.abstract
    del state, m, data, program
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference_rounds(cell, abstract, s, traffic, rounds_checked)
    log(f"[{name}] reference: {rounds_checked} rounds in "
        f"{time.perf_counter() - t_ref:.2f} s")
    from fedbench import check
    numbers = check.numbers(prog_readings, ref)
    checks = check.judge(numbers, work["check"]["limits"])
    correct = all(c["ok"] for c in checks.values()) and nonfinite == 0
    log("readings " + json.dumps(numbers))
    for k, c in checks.items():
        log(f"check {k} {c['value']:.6e} limit {c['limit']}")
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                      for k, c in checks.items()}
    return line


def metric_reader(name: str):
    """The ``read`` function of ``fedbench/metrics/<name>.py``."""
    return importlib.import_module(f"fedbench.metrics.{name}").read


@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric reader gets: the cell, the reduced trace
    of the traced window, the participants of each round in it, the
    weight layout, and the chip."""

    cell: Cell
    summary: Any
    participants: List[float]
    abstract: Any
    kind: str
    chips: int

    @property
    def rounds(self) -> int:
        return len(self.participants)
