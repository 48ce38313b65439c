"""Each entry's ``fetch`` hands over the program's integer counters
(``Answer.stats``), and the harness turns a window's counters into counts
per simulated step, summed over lanes and chips."""

import numpy as np
import pytest

from bench import harness
from tiny import SEED, tiny_cell


def _answer(cell):
    _, conn = harness.network(cell)
    store = cell.entry.build(conn, cell.config, cell.traffic)
    return cell.entry.fetch(cell.entry.call(
        store, *harness.call_seeds(SEED, 0, cell.traffic)))


@pytest.mark.parametrize("name,shape", [("q19_sugar", ()),
                                        ("q19_bg40", ()),
                                        ("f32_trials4", (4,))])
def test_fetch_hands_over_the_counters(name, shape):
    a = _answer(tiny_cell(name))
    assert set(a.stats) == {"slot_chunks"}
    chunks = a.stats["slot_chunks"]
    assert np.issubdtype(chunks.dtype, np.integer) and chunks.shape == shape
    assert (chunks >= 0).all() and chunks.sum() > 0


def test_distributed_fetch_sums_the_partitions():
    """The partitioned entry on one CPU device (one partition): its counter
    is one total over the partitions."""
    cell = tiny_cell("q19_p4_bg40")
    cell.config["partition"]["parts"] = 1
    a = _answer(cell)
    assert set(a.stats) == {"slot_chunks"}
    assert a.stats["slot_chunks"].shape == () and a.stats["slot_chunks"] > 0


def test_counters_per_step_over_lanes_and_calls():
    from bench.compare import Answer
    z = np.zeros((4, 3))

    def ans(chunks):
        return Answer(counts=z, v=z, g=z, refrac=z, dropped=np.zeros(4),
                      stats={"slot_chunks": np.asarray(chunks),
                             "only_here": np.asarray(1)})

    calls = [ans([1, 2, 3, 4]), ans([5, 0, 0, 0])]
    calls[1].stats.pop("only_here")
    assert harness._counters(calls, 200) == {"slot_chunks": 15 / 200}


def test_counters_keep_integer_counters_only():
    """The telemetry's ``compile_cache`` snapshot and any float entry of a
    result's ``stats`` are no program counter."""
    from bench import program
    got = program.counters({"slot_chunks": np.int32(3),
                            "h_saturated": np.zeros(4, np.int32),
                            "compile_cache": {"hits": 1, "misses": 2},
                            "mean": np.float32(0.5)})
    assert set(got) == {"slot_chunks", "h_saturated"}
    assert got["slot_chunks"] == 3 and got["h_saturated"].shape == (4,)
