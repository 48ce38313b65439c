"""``bench/work.py`` against hand counts at a tiny size."""

import pytest

from bench import work
from bench.devtrace import Reduction
from bench.harness import Window


def test_required_hand_count_fixed_point():
    # 10 neurons, 3 lane-steps, 7 events: per neuron-step 14 ops and
    # 4 words read + 4 written (32 B) + one ring byte read + one written
    ops, nbytes = work.required(10, 3, 7, fixed_point=True)
    assert ops == 3 * 10 * 14 + 7
    assert nbytes == 3 * 10 * 34 + 7 * 16


def test_required_hand_count_float():
    ops, nbytes = work.required(4, 5, 0, fixed_point=False)
    assert (ops, nbytes) == (4 * 5 * 10, 4 * 5 * 34)


def test_least_seconds_takes_the_binding_roof():
    peak = {"ops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_seconds(50, 30, peak) == pytest.approx(3.0)
    assert work.least_seconds(1000, 30, peak) == pytest.approx(10.0)


class _Kind:
    device_kind = "TPU v5 lite"


def test_measure_of_a_traced_window():
    red = Reduction(window_s=2.0, busy_s=1.5, collective_s=None, op_s={},
                    gaps=[])
    win = Window(setup_s=1.0, window_s=2.0, calls=2, steps=100, lanes=4,
                 dt_ms=0.1, chips=1)
    cfg = {"network": {"n_neurons": 1000},
           "model": {"fixed_point": False}}
    m = work.Measure.of(red, win, cfg, events=10_000, device=_Kind())
    ops, nbytes = work.required(1000, 2 * 100 * 4, 10_000, False)
    assert m.steps == 200
    assert m.least_s == pytest.approx(max(ops / 197e12, nbytes / 819e9))
