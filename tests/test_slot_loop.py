"""The activity-bounded slot loop at scan level: the SMOKE event run gives
the same spikes, state and drops whether the synapse budget is cut into
chunks or visited as one (``SLOT_CHUNKS`` patched to 1), on the
monolithic scan, the vmapped trials scan and the emulated P=4 event
scheme, in Q19.12 and float32; and the ``slot_chunks`` counter is the sum
over steps of ``ceil(min(requested, budget) / C)``."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.flywire import SMOKE
from repro.core import CapacityConfig, compaction, simulate, synthetic_flywire
from repro.core.dcsr import build_dcsr
from repro.core.distributed import DistConfig, simulate_distributed
from repro.core.exchange import build_dist_arrays
from repro.core.partition import even_partition
from repro.exp import ProbeSpec, build_scenario, run_trials
from test_compaction import np_two_level

# budgets that the 40 Hz background fills in several chunks and overruns
# at times: the whole network's, and a quarter of it per partition
CAP = CapacityConfig(spike_capacity=256, syn_budget=600)
CAP_P4 = CapacityConfig(spike_capacity=256, syn_budget=150)
SEEDS = (3, 4, 5, 6)
RASTER = ProbeSpec(raster=True)


@pytest.fixture(scope="module")
def smoke():
    c = synthetic_flywire(n=SMOKE.n_neurons,
                          target_synapses=SMOKE.target_synapses, seed=0)
    d = build_dcsr(c, even_partition(c, 4),
                   quantize_bits=SMOKE.sim.quantize_bits)
    return c, d


@pytest.fixture
def one_chunk(monkeypatch):
    """Runs a callable with the budget as one chunk, on programs traced
    afresh, and leaves no such program in the caches."""
    def run(fn):
        monkeypatch.setattr(compaction, "SLOT_CHUNKS", 1)
        jax.clear_caches()
        try:
            return fn()
        finally:
            monkeypatch.undo()
            jax.clear_caches()
    yield run
    jax.clear_caches()


def _cfg(fx: bool):
    return dataclasses.replace(SMOKE.sim, fixed_point=fx, capacity=CAP)


def _stimulus(c, cfg):
    return build_scenario("sugar_feeding", c, cfg, n_sugar=SMOKE.n_sugar,
                          rate_hz=SMOKE.sugar_rate_hz, background_hz=40.0)


def _trips(total: int, budget: int) -> int:
    return -(-min(total, budget) // compaction.slot_chunk(budget))


def _delayed(raster: np.ndarray, delay: int) -> np.ndarray:
    """[T, n] spikes the ring hands to step t: those of step t - delay."""
    out = np.zeros_like(raster)
    out[delay:] = raster[:-delay]
    return out


def _expected_chunks_monolithic(c, raster, delay) -> int:
    fo = np.diff(c.out_indptr)
    bcap = compaction.derived_block_capacity(c.n, CAP.spike_capacity)
    chunks = 0
    for spk in _delayed(raster, delay):
        kept = np_two_level(spk, CAP.spike_capacity, bcap)
        chunks += _trips(int(fo[kept[kept < c.n]].sum()), CAP.syn_budget)
    return chunks


def _expected_chunks_p4(d, raster, delay) -> int:
    """Every partition delivers the all-gathered kept events from its own
    source-major store, one trip count each."""
    P_, U = d.n_parts, d.part_size
    fo = np.diff(np.asarray(build_dist_arrays(d).out_indptr), axis=1)
    bcap = compaction.derived_block_capacity(U, CAP_P4.spike_capacity)
    chunks = 0
    for spk in _delayed(raster, delay):
        local = np.zeros(P_ * U, bool)
        local[d.perm[np.flatnonzero(spk)]] = True
        events = []
        for p in range(P_):
            kept = np_two_level(local[p * U:(p + 1) * U],
                                CAP_P4.spike_capacity, bcap)
            events.append(kept[kept < U] + p * U)
        events = np.concatenate(events)
        chunks += sum(_trips(int(fo[p][events].sum()), CAP_P4.syn_budget)
                      for p in range(P_))
    return chunks


def _run(path, c, d, cfg):
    stim = _stimulus(c, cfg)
    if path == "monolithic":
        r = simulate(c, cfg, SMOKE.t_steps, stimulus=stim, seed=SEEDS[0],
                     probes=RASTER)
    elif path == "trials":
        r = run_trials(c, cfg, SMOKE.t_steps, stimulus=stim, seeds=SEEDS,
                       probes=RASTER)
    else:
        r = simulate_distributed(
            d, DistConfig(sim=cfg, scheme="event", capacity=CAP_P4),
            SMOKE.t_steps, stimulus=stim, seed=SEEDS[0], probes=RASTER,
            emulate=True)
    return jax.tree.map(np.asarray, {
        "counts": r.counts, "state": tuple(r.state), "dropped": r.dropped,
        "raster": r.records["raster"], "slot_chunks": r.stats["slot_chunks"]})


@pytest.mark.parametrize("fx", [True, False], ids=["q19.12", "f32"])
@pytest.mark.parametrize("path", ["monolithic", "trials", "p4_emulate"])
def test_chunked_budget_equals_one_chunk(smoke, one_chunk, path, fx):
    c, d = smoke
    cfg = _cfg(fx)
    chunked = _run(path, c, d, cfg)
    whole = one_chunk(lambda: _run(path, c, d, cfg))
    for key in ("counts", "dropped", "raster"):
        np.testing.assert_array_equal(chunked[key], whole[key], err_msg=key)
    for a, b in zip(chunked["state"], whole["state"]):
        np.testing.assert_array_equal(a, b)
    assert int(np.sum(chunked["dropped"])) > 0      # the budget overran

    delay = cfg.params.delay_steps
    if path == "monolithic":
        want = _expected_chunks_monolithic(c, chunked["raster"], delay)
    elif path == "trials":
        want = [_expected_chunks_monolithic(c, lane, delay)
                for lane in chunked["raster"]]
    else:
        want = _expected_chunks_p4(d, chunked["raster"], delay)
    np.testing.assert_array_equal(chunked["slot_chunks"], want)
    # one chunk a step with anything to deliver: fewer trips, same result
    assert np.all(whole["slot_chunks"] < chunked["slot_chunks"])
