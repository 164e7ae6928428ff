"""What reads the program's scopes, spans and counters: the phase split
of a trace, on a synthetic trace with nested ops and scope paths
(hand-worked answers), against the busy time of ``fedbench.trace.reduce``
and on a profiler trace recorded on the CPU; scope paths from HLO text;
the compile-counter reader."""
import json
from pathlib import Path

import pytest

from fedbench import phases, trace
from fedbench.metrics import setup_compile_s

DATA = Path(__file__).parent / "data"


@pytest.fixture
def nested():
    return phases.from_dict(
        json.loads((DATA / "synthetic_phase_trace.json").read_text()))


def _as_trace(t: phases.PhaseTrace) -> trace.Trace:
    return trace.Trace({k: [op[:3] for op in v] for k, v in t.devices.items()},
                       [h for h in t.host if h[0].startswith("fedbench.")])


def test_nested_ops_count_once_for_the_innermost(nested):
    # TPU:0: while.1 [100,500) holds fusion.2 and fusion.3, all training:
    # 400 ns; cross-testing 100 + while.8's 90 ns around fusion.9, whose
    # 30 ns go to its own scope (score); a copy with no scope 30 ns;
    # the aggregate kernel 60 ns; fusion.7 lies outside the window.
    # TPU:1: training 300, cross-testing 100. Mean over the two chips:
    sp = phases.split(nested)
    assert sp.phase_s == pytest.approx({
        "fedtest.train": 350e-9, "fedtest.cross_test": 145e-9,
        "fedtest.score": 15e-9, "fedtest.aggregate": 30e-9,
        "unscoped": 15e-9})
    assert sp.busy_s == pytest.approx(555e-9)
    assert sp.window_s == pytest.approx(800e-9)
    assert sp.rounds == 2           # the third round starts after the window
    assert sp.ms_per_round()["fedtest.train"] == pytest.approx(175e-6)


def test_phases_add_up_to_the_busy_time_of_reduce(nested):
    s = trace.reduce(_as_trace(nested))
    assert phases.split(nested).busy_s == pytest.approx(s.busy_s)
    # the op sums count the loops and their bodies both
    assert sum(s.op_s.values()) / s.devices > s.busy_s
    old = trace.from_dict(
        json.loads((DATA / "synthetic_trace.json").read_text()))
    flat = phases.PhaseTrace(
        {k: [op + ("",) for op in v] for k, v in old.devices.items()},
        old.host)
    sp = phases.split(flat)
    assert sp.busy_s == pytest.approx(trace.reduce(old).busy_s)
    assert set(sp.phase_s) == {"unscoped"}


def test_idle_gaps_named_by_span_path(nested):
    # TPU:0 idles [650,700) inside the second dispatch's round span,
    # [500,520) in the host's wait and [880,900) in the global evaluation
    assert phases.split(nested).idle_gaps == [
        ("fedbench.dispatch/fedtest.round", pytest.approx(50e-9)),
        ("fedbench.wait", pytest.approx(20e-9)),
        ("fedtest.global_eval", pytest.approx(20e-9))]
    host = [("fedbench.window", 0, 100), ("fedbench.wait", 10, 20)]
    assert phases.span_path(host, 0, 40) == "fedbench.wait"
    assert phases.span_path(host, 50, 60) == "host"


def test_phase_of():
    assert phases.phase_of(
        "jit(_multi_round)/while/body/fedtest.cross_test/vmap(dot)") == \
        "fedtest.cross_test"
    assert phases.phase_of("jit(f)/transpose(jvp(conv))") == "unscoped"
    assert phases.phase_of("") == "unscoped"


HLO = """
ENTRY %main.9 (data.1: f32[8]) -> f32[8] {
  %data.1 = f32[8]{0} parameter(0), metadata={op_name="data"}
  %copy.2 = f32[8]{0} copy(%data.1), metadata={op_name="data"}
  %bitcast.3 = f32[8]{0} bitcast(%copy.2)
  %fusion.4 = f32[8]{0} fusion(%bitcast.3), kind=kLoop, calls=%f.1, \
metadata={op_name="jit(r)/fedtest.train/vmap()/gather" stack_frame_id=3}
  %copy.5 = f32[8]{0} copy(%fusion.4)
  ROOT %tuple.6 = (f32[8]{0}) tuple(%copy.5)
}
"""


def test_hlo_scopes_lend_a_users_phase_to_unscoped_ops():
    scopes = phases.hlo_scopes(HLO)
    train = "jit(r)/fedtest.train/vmap()/gather"
    # the argument's layout copy and the bitcast take the gather's scope
    assert scopes["fusion.4"] == train
    assert scopes["bitcast.3"] == train and scopes["copy.2"] == train
    # no user has a phase: the op stays unscoped
    assert phases.phase_of(scopes["copy.5"]) == "unscoped"
    assert phases.phase_of(scopes["tuple.6"]) == "unscoped"


def test_setup_compile_s_reads_the_compile_counters():
    import jax
    import jax.numpy as jnp
    before = setup_compile_s.read(None)
    jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.25)(jnp.ones((5, 3)))
    assert setup_compile_s.read(None) > before >= 0.0


def test_load_reads_program_spans_from_a_recorded_trace(tmp_path, capsys):
    import jax
    import jax.numpy as jnp
    from repro.utils import tracing
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("fedbench.window"):
        for step in range(2):
            with jax.profiler.TraceAnnotation("fedbench.dispatch"):
                with tracing.span(tracing.ROUND, step=step):
                    y = f(x)
            y.block_until_ready()
        with tracing.span(tracing.GLOBAL_EVAL):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = phases.load(str(tmp_path))
    names = [n for n, _, _ in t.host]
    assert names.count("fedtest.round") == 2
    assert names.count("fedtest.global_eval") == 1
    assert names.count("fedbench.window") == 1
    assert t.devices == {}          # the CPU has no TPU planes
    assert phases.split(t).rounds == 2
    (tmp_path / "round.hlo").write_text(HLO)
    assert phases.main([str(tmp_path), str(tmp_path / "round.hlo")]) == 0
    assert json.loads(capsys.readouterr().out)["rounds"] == 2
