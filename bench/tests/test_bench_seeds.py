"""``--seed`` draws a run's inputs, not its work: every seed simulates the
configuration's one network and, where the traffic fixes it, the same
stimulated population, with its own Poisson and background draws."""

import numpy as np
import pytest

from bench import harness, program
from bench.references import lif_network
from tiny import TINY_NETWORK


def tiny_cell(name):
    """A cell at the harness tests' size, with its own draws."""
    cell = harness.load_cell(name)
    cell.config["network"].update(TINY_NETWORK)
    return cell


@pytest.mark.parametrize("name", ["q19_sugar", "f32_trials4"])
def test_every_seed_drives_one_population(name):
    cell = tiny_cell(name)
    net, conn = harness.network(cell)
    cfg = program.sim_config(cell.config["model"], cell.traffic)
    pops = []
    for seed in (2**31 + 7, 2**32 + 11):
        stim_seed, _ = harness.call_seeds(seed, 0, cell.traffic)
        drive = program.stimulus(conn, cfg, cell.traffic, stim_seed)
        pops.append(np.sort(np.asarray(drive.parts[0].idx)))
    assert len(pops[0]) == cell.traffic["params"]["n_sugar"]
    np.testing.assert_array_equal(*pops)
    # the reference drives the same neurons, and each lane seed draws its
    # own arrivals on them
    tr = {**cell.traffic, "dt_ms": cell.config["model"]["lif"]["dt"]}
    (_, a), = lif_network.drive_events(net.n, tr, 400, 1, 5, None)
    (_, b), = lif_network.drive_events(net.n, tr, 400, 2, 6, None)
    np.testing.assert_array_equal(np.flatnonzero(a.any(0)), pops[0])
    np.testing.assert_array_equal(np.flatnonzero(b.any(0)), pops[0])
    assert (a != b).any()


def test_every_seed_draws_one_network():
    cell = tiny_cell("q19_bg40")
    a, _ = harness.network(cell)
    b, _ = harness.network(cell)
    for field in ("out_indptr", "out_indices", "out_weights"):
        np.testing.assert_array_equal(getattr(a, field), getattr(b, field))
    cell.config["network"]["seed"] += 1
    c, _ = harness.network(cell)
    assert not np.array_equal(a.out_indices, c.out_indices)


def test_a_traffic_without_a_population_seed_draws_it_per_call():
    cell = tiny_cell("q19_sugar")
    del cell.traffic["params"]["seed"]
    _, conn = harness.network(cell)
    cfg = program.sim_config(cell.config["model"], cell.traffic)
    idx = [np.asarray(program.stimulus(conn, cfg, cell.traffic, s)
                      .parts[0].idx) for s in (3, 4)]
    assert not np.array_equal(*idx)


def test_fixed_streams_are_one_set_in_a_seed_given_order():
    tr = harness.load_cell("f32_trials4").traffic
    assert tr["streams_seed"] == 0
    orders = set()
    for i in (-1, 0, 1, 2):
        calls = [harness.call_seeds(seed, i, tr)
                 for seed in (2**31 + 7, 2**32 + 11, 3, 4)]
        assert len({c[0] for c in calls}) == 1
        assert len({tuple(sorted(c[1])) for c in calls}) == 1
        assert len(set(calls[0][1])) == tr["lanes"]
        orders |= {tuple(c[1]) for c in calls}
    assert len(orders) > 4
    # without the key every seed draws its own streams
    free = {**tr, "streams_seed": None}
    a, b = (harness.call_seeds(s, 0, free) for s in (3, 4))
    assert not set(a[1]) & set(b[1])
