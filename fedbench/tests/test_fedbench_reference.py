"""The plain reference against the program's round entry points, at tiny
sizes on the CPU: ``FederatedTrainer.run_round`` for the CNN,
``PopulationTrainer.run_round`` for a cohort; and the control,
the reference in the precision below the configuration's, put in the
program's place, comes out as not correct."""
import jax
import numpy as np
import pytest

from fedbench import check, harness, weights
from fedbench import traffic as traffic_mod
from fedbench.reference import control
from fedbench.tests import tiny_cells


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_cells.make_root(tmp_path_factory.mktemp("tiny"))


def first_rounds(root, name, seed):
    cell = harness.load_cell(name, root)
    program = harness.Program(cell, jax.devices()[:1])
    s = harness.seeds(seed)
    traffic = traffic_mod.make(cell.work["generator"], cell.work["traffic"],
                               s["data"])
    state = program.state(harness.weights_for(program, s["weights"]),
                          jax.random.PRNGKey(s["run"]))
    _, prog = harness.drive_first_rounds(program, state,
                                         program.data(traffic),
                                         cell.work["check"]["rounds"])
    return cell, program, s, traffic, prog


@pytest.mark.parametrize("name", ["tiny-cnn", "tiny-cohort"])
def test_reference_matches_the_program(root, name):
    with jax.default_matmul_precision("highest"):
        cell, program, s, traffic, prog = first_rounds(root, name, 2**31 + 7)
        ref = harness.reference_rounds(cell, program.abstract, s, traffic,
                                       cell.work["check"]["rounds"])
    got = check.judge(check.numbers(prog, ref), tiny_cells.LIMITS)
    assert all(c["ok"] for c in got.values()), got
    # the rounds did work: the model moved, and the testers already tell
    # the models apart
    assert max(ref["updateN"]) > 0
    assert np.ptp(ref["scores"][-1]) > 0


def test_control_is_not_correct(root):
    with jax.default_matmul_precision("highest"):
        cell, program, s, traffic, _ = first_rounds(root, "tiny-cnn", 1)
        rounds = cell.work["check"]["rounds"]
        ref = harness.reference_rounds(cell, program.abstract, s, traffic,
                                       rounds)
        ctrl = harness.reference_rounds(cell, program.abstract, s, traffic,
                                        rounds,
                                        prec=control(cell.cfg))
    got = check.judge(check.numbers(ctrl, ref), tiny_cells.LIMITS)
    assert not all(c["ok"] for c in got.values()), got


def test_weights_follow_the_layout_and_the_seed():
    abstract = {"layers": {"slot_0": {"attn": {
        "wq": jax.ShapeDtypeStruct((2, 64, 32), np.float32),
        "bq": jax.ShapeDtypeStruct((2, 32), np.float32)}}},
        "norm": {"scale": jax.ShapeDtypeStruct((64,), np.float32)},
        "embed": jax.ShapeDtypeStruct((100, 64), "bfloat16")}
    a = weights.make(abstract, jax.random.PRNGKey(3))
    b = weights.make(abstract, jax.random.PRNGKey(3))
    c = weights.make(abstract, jax.random.PRNGKey(4))
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda x, y: bool((x == y).all()), a, b))
    assert not bool((a["embed"] == c["embed"]).all())
    assert a["embed"].dtype == np.dtype("bfloat16")
    assert (np.asarray(a["norm"]["scale"]) == 1).all()
    assert (np.asarray(a["layers"]["slot_0"]["attn"]["bq"]) == 0).all()
    wq = np.asarray(a["layers"]["slot_0"]["attn"]["wq"])
    # fan-in 64 after the stacked-layer axis, truncated at 2 sigma
    assert np.abs(wq).max() <= 2 * 64 ** -0.5 + 1e-6
    assert 0.5 * 64 ** -0.5 < wq.std() < 64 ** -0.5


def test_seeds_of_any_size_differ():
    a, b = harness.seeds(2**40 + 1), harness.seeds(1)
    assert a != b and harness.seeds(2**40 + 1) == a
    assert all(0 <= v < 2**32 for v in a.values())
