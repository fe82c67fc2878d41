"""A whole run of each grid cell at a CPU test's size: sound it is correct,
with the timed path broken underneath it is not, and the control (the
reference at three bfloat16 passes in the program's place) departs from
the reference far more than the program does."""
import pytest

from bench import harness
from bench.tests import _tiny

CELLS = ["cnn_fmnist.grid", "charrnn_shakespeare.grid"]
SEED = 3_000_000_019


def _run(cell):
    return harness.run_cell(cell, SEED, 0.5, False, require_tpu=False,
                            overrides=_tiny.overrides(cell))


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    line = _run(cell)
    assert line["correct"] is True, line["checks"]
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(_tiny.FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    _tiny.FAULTS[fault](monkeypatch)
    line = _run(cell)
    assert line["correct"] is False, line["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_departs_from_the_reference(cell):
    _, rows, c, system = harness.measure(
        cell, SEED, 0.5, False, require_tpu=False,
        overrides=_tiny.overrides(cell))
    sound = harness.check(rows, c, system)
    control = harness.check(rows, c, system, control="bf16_3x")
    assert control["loss_gap"] > 10 * sound["loss_gap"]
