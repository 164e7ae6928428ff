"""The readings that limits are set from: the program against the
reference, and the control and a planted fault against it, per seed."""
import jax

from fedbench import calibrate
from fedbench.tests import tiny_cells


def test_readings_of_the_program_the_control_and_a_fault(tmp_path):
    root = tiny_cells.make_root(tmp_path)
    with jax.default_matmul_precision("highest"):
        rows = list(calibrate.readings("tiny-cnn", [1], require_chip=False,
                                       root=root, log=lambda line: None))
    (row,) = rows
    assert set(row) >= {"seed", "program", "control", "half_batch",
                        "altered_report", "gaps1"}
    assert "no_exchange" not in row          # one chip: nothing to leave out
    limits = tiny_cells.LIMITS
    assert all(row["program"][k] <= v for k, v in limits.items())
    for kind in ("control", "half_batch", "altered_report"):
        assert any(row[kind][k] > v for k, v in limits.items()), row[kind]
