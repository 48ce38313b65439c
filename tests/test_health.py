"""Resilience layer (PR 6 acceptance): chunked supervised scans,
in-scan health sentinels, checkpoint/resume, and fault-injected recovery.

Pins: (a) ``chunk_steps`` is bit-neutral — chunked == monolithic scan,
bitwise, on float32 AND Q19.12, monolithic and distributed (P=4 emulate);
(b) a killed run resumed from its checkpoints reproduces the
uninterrupted run's counts/raster/records bit-for-bit; (c) poison (NaN)
raises :class:`SimulationHealthError` naming the step and counter; (d) a
drop-rate breach under ``run_resilient`` escalates capacity and converges
to a lossless run bit-equal to an amply-provisioned reference; (e) an
injected partition failure (``faulty`` exchange scheme) is detected and
recovered bit-identically; (f) the checkpoint satellites — dtype-checked
restore, joinable async saves — and the non-finite-masked parity
statistic; (g) supervision backoff — jittered-exponential, capped delays
between restarts/escalations, surfaced as ``backoff_s`` on the telemetry
events, with ``backoff=None`` restoring immediate retry.
"""

import dataclasses
import random
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import (CapacityConfig, FaultSpec, HealthConfig, SimConfig,
                        SimulationHealthError, configure_faulty, parity,
                        run_resilient, simulate, synthetic_flywire)
from repro.core.health import BackoffPolicy
from repro.core.dcsr import build_dcsr
from repro.core.distributed import DistConfig, simulate_distributed
from repro.core.exchange.faulty import ExchangeFault
from repro.core.health import health_step_stats
from repro.core.neuron import LIFState
from repro.core.partition import even_partition
from repro.exp import ProbeSpec, StepCurrent, per_neuron


@pytest.fixture(scope="module")
def setup():
    c = synthetic_flywire(n=400, target_synapses=8_000, seed=0)
    sugar = np.arange(80)
    d = build_dcsr(c, even_partition(c, 4))
    return c, sugar, d


PROBES = ProbeSpec(raster=True, pop_rate=True)


def _run(c, cfg, t, sugar, **kw):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return simulate(c, cfg, t, sugar_neurons=sugar, seed=3,
                        probes=PROBES, **kw)


def _run_dist(d, dcfg, t, sugar, **kw):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return simulate_distributed(d, dcfg, t, sugar_neurons=sugar, seed=3,
                                    emulate=True, probes=PROBES, **kw)


def _assert_bitwise(a, b):
    assert np.array_equal(np.asarray(a.counts), np.asarray(b.counts))
    assert np.array_equal(np.asarray(a.raster), np.asarray(b.raster))
    for k in a.records:
        assert np.array_equal(np.asarray(a.records[k]),
                              np.asarray(b.records[k])), k
    assert np.array_equal(np.asarray(a.state.v), np.asarray(b.state.v))
    assert int(np.asarray(a.dropped).sum()) == int(np.asarray(b.dropped).sum())


# --------------------------------------------------------------------------
# (a) chunking is bit-neutral
# --------------------------------------------------------------------------

@pytest.mark.parametrize("engine,fx", [("csr", False), ("event", False),
                                       ("event", True)])
def test_chunked_bit_identity_monolithic(setup, engine, fx):
    """ceil(T/K) reuses of one K-step program == the monolithic scan,
    bitwise, including a ragged tail chunk (K does not divide T)."""
    c, sugar, _ = setup
    cfg = SimConfig(engine=engine, fixed_point=fx)
    ref = _run(c, cfg, 50, sugar)
    chk = _run(c, cfg, 50, sugar, chunk_steps=16)     # 16+16+16+2
    _assert_bitwise(ref, chk)


def test_chunked_bit_identity_distributed(setup):
    c, sugar, d = setup
    dcfg = DistConfig(sim=SimConfig(engine="event"), scheme="event")
    ref = _run_dist(d, dcfg, 50, sugar)
    chk = _run_dist(d, dcfg, 50, sugar, chunk_steps=16)
    _assert_bitwise(ref, chk)


def test_chunked_rejects_trials(setup):
    c, sugar, d = setup
    from repro.exp import run_dist_trials
    from repro.core.distributed import _run_partitioned
    dcfg = DistConfig(sim=SimConfig(engine="event"), scheme="event")
    with pytest.raises(ValueError, match="trial-batched"):
        _run_partitioned(d, dcfg, 10, jnp.zeros((4, 2, 2), jnp.uint32),
                         None, None, None, None, True, trials=True,
                         chunk_steps=5)


# --------------------------------------------------------------------------
# sentinels
# --------------------------------------------------------------------------

def test_health_step_stats_counts_nonfinite():
    sim = SimConfig(health=HealthConfig())
    v = jnp.array([0.0, jnp.nan, jnp.inf, 1.0])
    g = jnp.array([0.0, 0.0, 0.0, -jnp.inf])
    lif = LIFState(v=v, g=g, refrac=jnp.zeros(4, jnp.int32))
    assert int(health_step_stats(lif, sim)["h_nonfinite"]) == 3
    # disabled -> no counters, no pytree change
    assert health_step_stats(lif, SimConfig()) == {}


def test_health_step_stats_counts_saturation():
    sim = SimConfig(fixed_point=True, health=HealthConfig(sat_margin_bits=2))
    big = np.int32(1 << 29)
    v = jnp.array([0, big, -big, np.int32(-(2 ** 31))], jnp.int32)
    g = jnp.zeros(4, jnp.int32)
    lif = LIFState(v=v, g=g, refrac=jnp.zeros(4, jnp.int32))
    # int32 min must count (no abs-overflow wraparound)
    assert int(health_step_stats(lif, sim)["h_saturated"]) == 3


def test_stats_surface_on_results(setup):
    c, sugar, d = setup
    cfg = SimConfig(engine="event", health=HealthConfig())
    r = _run(c, cfg, 20, sugar, chunk_steps=10)
    assert int(r.stats["h_nonfinite"]) == 0
    dcfg = DistConfig(sim=cfg, scheme="event")
    rd = _run_dist(d, dcfg, 20, sugar, chunk_steps=10)
    assert int(np.asarray(rd.stats["h_nonfinite"]).sum()) == 0


# --------------------------------------------------------------------------
# (c) poison raises, naming step and counter
# --------------------------------------------------------------------------

def test_nan_poison_raises_named(setup):
    c, _, _ = setup
    cfg = SimConfig(engine="csr", health=HealthConfig())
    # NaN drive from step 0 (NaN * gate stays NaN — exactly the silent
    # poison the sentinels exist for)
    poison = StepCurrent(per_neuron([0], np.nan, c.n), target="v")
    with pytest.raises(SimulationHealthError, match="nonfinite") as ei:
        simulate(c, cfg, 40, stimulus=poison, chunk_steps=10)
    # detected at the first chunk boundary
    assert ei.value.kind == "nonfinite"
    assert ei.value.step == 10
    assert ei.value.value > 0


def test_rate_envelope_breach(setup):
    c, sugar, _ = setup
    cfg = SimConfig(engine="event",
                    health=HealthConfig(rate_hi_hz=1e-6))
    with pytest.raises(SimulationHealthError, match="rate_envelope"):
        _run(c, cfg, 60, sugar, chunk_steps=20)


def test_poison_is_not_recoverable(setup):
    """run_resilient must re-raise poison instead of restart-looping on a
    deterministic corruption."""
    c, _, _ = setup
    cfg = SimConfig(engine="csr", health=HealthConfig())
    poison = StepCurrent(per_neuron([0], np.nan, c.n), t_on=2, target="v")
    calls = []

    def attempt(resume, cap):
        calls.append(resume)
        return simulate(c, cfg, 20, stimulus=poison, chunk_steps=10)

    with pytest.raises(SimulationHealthError, match="nonfinite"):
        run_resilient(attempt)
    assert len(calls) == 1


# --------------------------------------------------------------------------
# (b) kill-and-resume bit-identity
# --------------------------------------------------------------------------

@pytest.mark.parametrize("async_save", [False, True])
def test_kill_and_resume_bit_identity(setup, tmp_path, async_save):
    c, sugar, _ = setup
    cfg = SimConfig(engine="event")
    ref = _run(c, cfg, 50, sugar, chunk_steps=16)
    td = str(tmp_path / "ck")
    # "kill" after 2 chunks: a partial run leaving only its checkpoints
    _run(c, cfg, 32, sugar, chunk_steps=16, checkpoint_dir=td,
         async_checkpoint=async_save)
    res = _run(c, cfg, 50, sugar, chunk_steps=16, checkpoint_dir=td,
               resume=True, async_checkpoint=async_save)
    _assert_bitwise(ref, res)


def test_kill_and_resume_distributed(setup, tmp_path):
    c, sugar, d = setup
    dcfg = DistConfig(sim=SimConfig(engine="event"), scheme="event")
    ref = _run_dist(d, dcfg, 50, sugar, chunk_steps=16)
    td = str(tmp_path / "ck")
    _run_dist(d, dcfg, 32, sugar, chunk_steps=16, checkpoint_dir=td)
    res = _run_dist(d, dcfg, 50, sugar, chunk_steps=16, checkpoint_dir=td,
                    resume=True)
    _assert_bitwise(ref, res)


def test_resume_q19_12_dtype_guard(setup, tmp_path):
    """A Q19.12 checkpoint restored into a float-path template must raise,
    not silently cast (the satellite bugfix, end to end)."""
    c, sugar, _ = setup
    td = str(tmp_path / "ck")
    _run(c, SimConfig(engine="event", fixed_point=True), 32, sugar,
         chunk_steps=16, checkpoint_dir=td)
    with pytest.raises(ValueError, match="dtype mismatch"):
        _run(c, SimConfig(engine="event", fixed_point=False), 50, sugar,
             chunk_steps=16, checkpoint_dir=td, resume=True)


# --------------------------------------------------------------------------
# (d) drop-rate breach -> capacity escalation -> lossless convergence
# --------------------------------------------------------------------------

def test_drop_rate_escalation_converges_lossless(setup, tmp_path):
    c, sugar, _ = setup
    ample = SimConfig(engine="event",
                      capacity=CapacityConfig(512, 65_536))
    ref = _run(c, ample, 80, sugar)
    assert int(ref.dropped) == 0

    tiny = CapacityConfig(spike_capacity=4, syn_budget=64)
    hc = HealthConfig(max_drop_rate=0.0)
    td = str(tmp_path / "ck")
    caps = []

    def attempt(resume, cap):
        cap = cap or tiny
        caps.append(cap)
        cfg = SimConfig(engine="event", capacity=cap, health=hc)
        return _run(c, cfg, 80, sugar, chunk_steps=20, checkpoint_dir=td,
                    resume=resume is not None)

    out = run_resilient(attempt, checkpoint_dir=td, capacity=tiny,
                        max_escalations=10)
    assert len(caps) > 1                      # it did breach and escalate
    assert caps[-1].syn_budget > tiny.syn_budget
    assert int(out.dropped) == 0              # converged lossless
    _assert_bitwise(ref, out)                 # ... and bit-equal to ample


def test_escalation_declined_without_capacity(setup, tmp_path):
    """No base capacity -> the default policy cannot escalate; the breach
    must surface instead of looping."""
    c, sugar, _ = setup
    hc = HealthConfig(max_drop_rate=0.0)
    tiny = CapacityConfig(spike_capacity=4, syn_budget=64)

    def attempt(resume, cap):
        cfg = SimConfig(engine="event", capacity=tiny, health=hc)
        return _run(c, cfg, 80, sugar, chunk_steps=20)

    with pytest.raises(SimulationHealthError, match="drop_rate"):
        run_resilient(attempt)                # capacity=None


# --------------------------------------------------------------------------
# (e) fault injection at the exchange layer
# --------------------------------------------------------------------------

def test_faulty_partition_failure_recovered(setup, tmp_path):
    c, sugar, d = setup
    clean = DistConfig(sim=SimConfig(engine="event"), scheme="event")
    ref = _run_dist(d, clean, 50, sugar, chunk_steps=16)

    configure_faulty(inner="event",
                     spec=FaultSpec(partition=1, fail_at=(20,)))
    fcfg = DistConfig(sim=SimConfig(engine="event"), scheme="faulty")
    td = str(tmp_path / "ck")
    attempts = []

    def attempt(resume, cap):
        attempts.append(resume)
        return _run_dist(d, fcfg, 50, sugar, chunk_steps=16,
                         checkpoint_dir=td, resume=resume is not None)

    out = run_resilient(attempt, checkpoint_dir=td)
    assert len(attempts) == 2                 # failed once, recovered once
    assert attempts[1] == 16                  # resumed from the checkpoint
    _assert_bitwise(ref, out)


def test_faulty_failure_exceeds_restarts(setup, tmp_path):
    configure_faulty(inner="event",
                     spec=FaultSpec(partition=0, fail_at=(4, 20, 36)))
    c, sugar, d = setup
    fcfg = DistConfig(sim=SimConfig(engine="event"), scheme="faulty")
    td = str(tmp_path / "ck")

    def attempt(resume, cap):
        return _run_dist(d, fcfg, 50, sugar, chunk_steps=16,
                         checkpoint_dir=td, resume=resume is not None)

    with pytest.raises(ExchangeFault):
        run_resilient(attempt, checkpoint_dir=td, max_restarts=1)


def test_faulty_payload_drop_is_counted(setup):
    """A lost payload is a counted loss: the failed partition's whole
    outgoing fan-out lands in the exact ``dropped`` counter — at every
    faulty step, the global fan-out of each spike partition 0 sent."""
    c, sugar, d = setup
    clean = DistConfig(sim=SimConfig(engine="event"), scheme="event")
    ref = _run_dist(d, clean, 60, sugar)
    fault_steps = range(20, 50)
    configure_faulty(inner="event",
                     spec=FaultSpec(partition=0,
                                    drop_payload_at=tuple(fault_steps)))
    fcfg = DistConfig(sim=SimConfig(engine="event"), scheme="faulty")
    out = _run_dist(d, fcfg, 60, sugar)
    configure_faulty()   # reset to clean defaults for other tests
    assert int(ref.dropped) == 0              # the clean run is lossless
    # step t exchanges the spikes of step t - delay; partition 0's sources
    # in original ids, each costing its whole fan-out
    delay = SimConfig().params.delay_steps
    on_p0 = d.perm // d.part_size == 0
    fan_out = np.diff(c.out_indptr)
    raster = np.asarray(out.raster)
    lost = sum(int((raster[t - delay] & on_p0) @ fan_out)
               for t in fault_steps if t >= delay)
    assert lost > 0
    assert int(out.dropped) == lost


def test_faulty_configure_guards():
    with pytest.raises(ValueError, match="cannot wrap"):
        configure_faulty(inner="faulty")
    with pytest.raises(ValueError, match="cannot wrap"):
        configure_faulty(inner="local")
    configure_faulty()   # reset to clean defaults for other tests


# --------------------------------------------------------------------------
# (f) satellites: parity non-finite masking
# --------------------------------------------------------------------------

def test_parity_masks_nonfinite():
    a = np.array([1.0, 2.0, np.nan, 4.0, np.inf])
    b = np.array([1.0, 2.0, 3.0, np.nan, 5.0])
    s = parity(a, b)
    assert s.n_nonfinite == 3
    assert np.isfinite(s.rmse_hz) and np.isfinite(s.pearson_r)
    assert s.n_active == 2                    # only finite-in-both survive
    assert "nonfinite=3" in s.summary()


def test_parity_finite_behavior_unchanged():
    rng = np.random.default_rng(0)
    a = rng.uniform(0, 20, 200)
    b = a + rng.normal(0, 0.1, 200)
    s = parity(a, b)
    assert s.n_nonfinite == 0
    assert s.n_active == int(((a > 0.5) | (b > 0.5)).sum())
    assert s.rmse_hz < 0.5 and s.pearson_r > 0.99


# --------------------------------------------------------------------------
# (g) supervision backoff
# --------------------------------------------------------------------------

def test_backoff_policy_exponential_capped_deterministic():
    p = BackoffPolicy(base_s=0.1, factor=2.0, cap_s=0.9, jitter=0.0)
    assert [p.delay(a) for a in range(1, 6)] == [
        pytest.approx(0.1), pytest.approx(0.2), pytest.approx(0.4),
        pytest.approx(0.8), pytest.approx(0.9)]        # clamped at cap_s
    # jitter widens around the nominal delay, deterministically per rng seed
    j = BackoffPolicy(base_s=0.1, factor=2.0, cap_s=0.9, jitter=0.5)
    got = [j.delay(2, rng=random.Random(7)) for _ in range(3)]
    assert got[0] == got[1] == got[2]
    assert 0.1 <= got[0] <= 0.3 and got[0] != pytest.approx(0.2)
    assert j.delay(2, rng=random.Random(7)) != j.delay(2, rng=random.Random(8))


def test_run_resilient_backoff_delays_and_events():
    """Crash-looping runs wait out jittered-exponential delays between
    restarts, and each restart/escalation event carries the applied
    ``backoff_s`` so incident timelines show the supervisor's pacing."""
    boom = [3]
    waits, events = [], []

    def attempt(resume, cap):
        if boom[0]:
            boom[0] -= 1
            raise RuntimeError("transient")
        return "ok"

    with obs.telemetry(events.append, validate=True):
        out = run_resilient(
            attempt, max_restarts=3,
            backoff=BackoffPolicy(base_s=0.05, factor=2.0, cap_s=0.08,
                                  jitter=0.0),
            sleep=waits.append)
    assert out == "ok"
    assert waits == [pytest.approx(0.05), pytest.approx(0.08),
                     pytest.approx(0.08)]              # exponential, capped
    restarts = [e for e in events if e["type"] == "restart"]
    assert [r["attempt"] for r in restarts] == [1, 2, 3]
    assert [r["backoff_s"] for r in restarts] == [
        pytest.approx(0.05), pytest.approx(0.08), pytest.approx(0.08)]
    assert all(r["error"] == "RuntimeError" for r in restarts)


def test_run_resilient_backoff_none_is_immediate():
    boom = [2]
    waits = []

    def attempt(resume, cap):
        if boom[0]:
            boom[0] -= 1
            raise RuntimeError("transient")
        return "ok"

    assert run_resilient(attempt, backoff=None,
                         sleep=waits.append) == "ok"
    assert waits == []


def test_run_resilient_escalation_event_carries_backoff(setup, tmp_path):
    """Drop-rate escalation paces its retries through the same policy and
    stamps the chosen delay on the ``escalation`` event."""
    c, sugar, _ = setup
    hc = HealthConfig(max_drop_rate=0.0)
    tiny = CapacityConfig(spike_capacity=4, syn_budget=64)
    waits, events = [], []

    def attempt(resume, cap):
        cfg = SimConfig(engine="event", capacity=cap or tiny, health=hc)
        return _run(c, cfg, 80, sugar, chunk_steps=20)

    with obs.telemetry(events.append, validate=True):
        out = run_resilient(attempt, checkpoint_dir=str(tmp_path / "ck"),
                            capacity=tiny, max_escalations=10,
                            backoff=BackoffPolicy(base_s=0.01, factor=2.0,
                                                  cap_s=0.02, jitter=0.0),
                            sleep=waits.append)
    assert int(out.dropped) == 0
    esc = [e for e in events if e["type"] == "escalation"]
    assert esc and all(e["kind"] == "drop_rate" for e in esc)
    assert [e["backoff_s"] for e in esc] == [pytest.approx(w) for w in waits]
    assert waits[0] == pytest.approx(0.01)
    assert all(w <= 0.02 + 1e-9 for w in waits)
