"""``bench/work.py`` against hand counts at a tiny size."""

import json
import os

import pytest

from bench import harness, work
from bench.devtrace import Reduction
from bench.harness import Window

# the two-state LIF's required work, as the flywire configurations state it
LIF_Q19 = {"neuron_bytes": 34, "neuron_ops": 14, "event_bytes": 16,
           "event_ops": 1}
LIF_F32 = {**LIF_Q19, "neuron_ops": 10}


def test_required_hand_count_fixed_point():
    # 10 neurons, 3 lane-steps, 7 events: per neuron-step 14 ops and
    # 4 words read + 4 written (32 B) + one ring byte read + one written
    ops, nbytes = work.required(LIF_Q19, 10, 3, 7)
    assert ops == 3 * 10 * 14 + 7
    assert nbytes == 3 * 10 * 34 + 7 * 16


def test_required_hand_count_float():
    ops, nbytes = work.required(LIF_F32, 4, 5, 0)
    assert (ops, nbytes) == (4 * 5 * 10, 4 * 5 * 34)


def test_required_counts_events_by_the_block():
    spec = {"neuron_bytes": 3, "neuron_ops": 2, "event_bytes": 5,
            "event_ops": 7}
    assert work.required(spec, 10, 2, 4) == (2 * 10 * 2 + 4 * 7,
                                             2 * 10 * 3 + 4 * 5)


@pytest.mark.parametrize("name,block", [("flywire_q19", LIF_Q19),
                                        ("flywire_q19_p4", LIF_Q19),
                                        ("flywire_f32", LIF_F32)])
def test_configuration_states_the_lif_work(name, block):
    """Each configuration's ``work`` block gives the counts of the model it
    runs: the two-state LIF, 14 operations a neuron-step in Q19.12."""
    with open(os.path.join(harness.BENCH, "configs", f"{name}.json")) as f:
        config = json.load(f)
    assert config["work"] == block
    assert block["neuron_ops"] == (14 if config["model"]["fixed_point"]
                                   else 10)
    n, lane_steps, events = 139_255, 4_000, 123_456_789
    assert work.required(config["work"], n, lane_steps, events) == (
        lane_steps * n * block["neuron_ops"] + events,
        lane_steps * n * 34 + events * 16)


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
    m = work.Measure.of(red, win, LIF_F32, 1000, events=10_000,
                        device=_Kind(), layers={"delivery": 0.5},
                        counters={"slot_chunks": 0.25})
    ops, nbytes = work.required(LIF_F32, 1000, 2 * 100 * 4, 10_000)
    assert m.steps == 200
    assert m.least_s == pytest.approx(max(ops / 197e12, nbytes / 819e9))
    assert m.layers == {"delivery": 0.5}
    assert m.counters == {"slot_chunks": 0.25}
