"""The trace reduction, on a synthetic trace with hand-worked answers and
on a real profiler trace recorded on the CPU."""
import json
import types
from pathlib import Path

import pytest

from fedbench import trace
from fedbench.metrics import device_idle_share

DATA = Path(__file__).parent / "data" / "synthetic_trace.json"


@pytest.fixture
def summary():
    return trace.reduce(trace.from_dict(json.loads(DATA.read_text())))


def test_busy_and_window(summary):
    # TPU:0 busy [100,400) [500,700) [900,950) = 550 ns; TPU:1 [100,500)
    # [600,720) = 520 ns; the window annotation spans [100, 1000)
    assert summary.window_s == pytest.approx(900e-9)
    assert summary.busy_s == pytest.approx(535e-9)
    assert summary.devices == 2 and summary.windows == 1


def test_ops_are_clipped_to_the_window_and_named_by_instruction(summary):
    # fusion: 200 + 150 on TPU:0, 400 + the clipped [100,150) on TPU:1;
    # the fusion at 1000 lies outside the window
    assert summary.op_s["fusion.1"] == pytest.approx(350e-9)
    assert summary.kernel_s("fusion") == pytest.approx(800e-9)
    assert summary.kernel_s("weighted_aggregate") == pytest.approx(100e-9)
    assert summary.kernel_s("flash_attention") is None
    assert summary.collective_s == pytest.approx(170e-9)
    # per chip: the mean over the two chips
    assert summary.top_ops(2) == [("fusion.2", pytest.approx(200e-9)),
                                  ("fusion.1", pytest.approx(175e-9))]


def test_idle_gaps_named_by_host_span(summary):
    # TPU:0 idles [700,900), [400,500), [950,1000); the tie for [400,500)
    # between the wait and a dispatch goes to the shorter (inner) span, and
    # no harness span covers the last gap
    assert summary.idle_gaps == [
        ("fedbench.wait", pytest.approx(200e-9)),
        ("fedbench.dispatch", pytest.approx(100e-9)),
        ("host", pytest.approx(50e-9))]


def test_base_name():
    assert trace.base_name("weighted_aggregate.3") == "weighted_aggregate"
    assert trace.base_name(
        "%weighted_aggregate.12 = f32[1,8]{1,0} custom-call(f32[4,8] %x)"
    ) == "weighted_aggregate"
    assert trace.instruction("%while.44 = (s32[]) while(%t)") == "while.44"
    assert trace.base_name("all-gather-start.1.2") == "all-gather-start"
    assert trace.base_name("fusion") == "fusion"


def test_metric_readers_on_the_synthetic_trace(summary):
    ctx = types.SimpleNamespace(summary=summary, rounds=3)
    assert device_idle_share.read(ctx) == pytest.approx(
        100 * (1 - 535 / 900))
    empty = trace.reduce(trace.Trace({}, []))
    ctx = types.SimpleNamespace(summary=empty, rounds=3)
    assert device_idle_share.read(ctx) is None


def test_load_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("fedbench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("fedbench.dispatch"):
                y = f(x)
            with jax.profiler.TraceAnnotation("fedbench.wait"):
                y.block_until_ready()
    jax.profiler.stop_trace()
    t = trace.load(str(tmp_path))
    names = [n for n, _, _ in t.host]
    assert names.count("fedbench.dispatch") == 2
    assert names.count("fedbench.window") == 1
    assert t.devices == {}          # the CPU has no TPU planes
    s = trace.reduce(t)
    assert s.windows == 1 and s.busy_s == 0.0
