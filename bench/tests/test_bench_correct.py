"""The comparison that decides ``correct``, at a size a CPU test holds:
each one-chip cell's run of the simulator comes out correct, and its
control (the reference one precision step lower, in the program's place)
comes out not correct."""

import pytest

from tiny import ControlEntry, run, tiny_cell

ONE_CHIP = ["q19_sugar", "q19_bg40", "f32_trials4"]


@pytest.mark.parametrize("name", ONE_CHIP)
def test_simulator_is_correct(name):
    r = run(tiny_cell(name))
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert r["device"]["platform"] == "cpu"


@pytest.mark.parametrize("name", ONE_CHIP)
def test_control_is_not_correct(name):
    cell = tiny_cell(name)
    cell.entry = ControlEntry(cell)
    r = run(cell)
    assert r["correct"] is False, r["checks"]
    assert r["failed"] == 1
