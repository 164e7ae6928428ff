"""Tracing the FedTest round: device scopes on every heavy op of the
compiled round, host spans that cost no device sync, and the compile
counters (``repro.utils.tracing``)."""
import re

import jax
import pytest

from repro.config import FedConfig, TrainConfig
from repro.configs import get_config
from repro.core.engine import FederatedTrainer, PopulationTrainer
from repro.data import MNIST_LIKE, make_federated_image_dataset
from repro.data.population import DensePopulationData
from repro.models import build_model
from repro.utils import tracing
from repro.utils.tracing import _CompileCounters

N = 4
HEAVY = ("convolution", "dot", "reduce", "while", "custom-call")
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?\S+\s*=\s*.*?\s([a-z][\w-]*)\(")


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("fedtest-cnn-mnist").replace(cnn_channels=(4, 8, 8),
                                                  cnn_hidden=16)
    model = build_model(cfg)
    data = make_federated_image_dataset(MNIST_LIKE, N, num_samples=400,
                                        global_test=100, seed=0)
    tc = TrainConfig(optimizer="sgd", lr=0.1, schedule="constant",
                     batch_size=8, grad_clip=0.0, remat=False)
    return model, data, tc


def _trainer(setup, engine):
    """A round with every optional step on: an attack, sampling, faults
    and a compressed exchange, and on the population engine a cohort."""
    model, data, tc = setup
    fed = dict(num_users=N, num_testers=2, local_steps=2, attack="sign_flip",
               num_malicious=1, participation=0.75, fault="dropout",
               compressor="int8")
    if engine == "dense":
        return (FederatedTrainer(model, FedConfig(**fed), tc, eval_batch=16),
                data)
    return (PopulationTrainer(model, FedConfig(cohort=3, **fed), tc,
                              eval_batch=16), DensePopulationData(data))


@pytest.mark.parametrize("engine", ["dense", "population"])
def test_every_heavy_op_of_the_round_carries_a_phase_scope(setup, engine):
    tr, data = _trainer(setup, engine)
    state = tr.init(jax.random.PRNGKey(0))
    hlo = tr.compile_driver(state, data).as_text()
    seen, bare = set(), []
    for line in hlo.splitlines():
        m = INSTRUCTION.match(line)
        if not m:
            continue
        scope = re.search(r'op_name="([^"]*)"', line)
        phases = [p for p in tracing.PHASES
                  if scope and f"/{p}/" in scope.group(1) + "/"]
        seen.update(phases)
        if m.group(1) in HEAVY and len(phases) != 1:
            bare.append(line.strip()[:160])
    assert not bare, f"{len(bare)} heavy ops outside one phase: {bare[:5]}"
    assert seen == set(tracing.PHASES), sorted(set(tracing.PHASES) - seen)


@pytest.mark.parametrize("engine", ["dense", "population"])
def test_run_round_moves_nothing_to_the_host(setup, engine):
    tr, data = _trainer(setup, engine)
    state = tr.init(jax.random.PRNGKey(0))
    state, _ = tr.run_round(state, data)          # compiles
    with jax.transfer_guard_device_to_host("disallow"):
        for _ in range(2):
            state, metrics = tr.run_round(state, data)
    jax.block_until_ready((state, metrics))


def _compiles(stats, fun):
    return stats["compile"].get(fun, {"count": 0})["count"]


def test_compile_counter_rises_on_the_first_round_only(setup):
    tr, data = _trainer(setup, "dense")
    state = tr.init(jax.random.PRNGKey(0))
    jax.block_until_ready(state)
    before = tracing.compile_stats()
    state, m = tr.run_round(state, data)
    jax.block_until_ready((state, m))
    first = tracing.compile_stats()
    state, m = tr.run_round(state, data)
    jax.block_until_ready((state, m))
    second = tracing.compile_stats()
    fun = "jit(_round_body)"
    assert _compiles(first, fun) == _compiles(before, fun) + 1
    assert first["trace"]["_round_body"]["count"] == (
        before["trace"].get("_round_body", {"count": 0})["count"] + 1)
    assert first["seconds"] > before["seconds"]
    assert second["compile"] == first["compile"]
    assert second["seconds"] == first["seconds"]


def test_compile_seconds_count_nested_events_once():
    c = _CompileCounters()
    trace = "/jax/core/compile/jaxpr_trace_duration"
    # an inner function's trace ends inside its caller's, then a lowering
    # and a compile follow; a cache hit is counted, other events are not
    c.on_span(trace, 1.0, 1.5, fun_name="inner")
    c.on_span(trace, 0.5, 2.0, fun_name="outer")
    c.on_span("/jax/core/compile/jaxpr_to_mlir_module_duration", 2.0, 2.5,
              fun_name="jit(outer)")
    c.on_span("/jax/core/compile/backend_compile_duration", 3.0, 7.0,
              fun_name="jit(outer)")
    c.on_span("/jax/some/other_duration", 10.0, 20.0, fun_name="x")
    c.on_event("/jax/compilation_cache/cache_hits")
    c.on_event("/jax/compilation_cache/compile_requests_use_cache")
    s = c.snapshot()
    assert s["trace"] == {"inner": {"count": 1, "seconds": 0.5},
                          "outer": {"count": 1, "seconds": 1.5}}
    assert s["compile"]["jit(outer)"] == {"count": 1, "seconds": 4.0}
    assert s["seconds"] == pytest.approx(2.0 + 4.0)
    assert (s["cache_hits"], s["cache_misses"]) == (1, 0)


def test_host_spans_need_no_profiler():
    with tracing.span(tracing.GLOBAL_EVAL):
        with tracing.span(tracing.ROUND, step=7):
            pass
