"""The unified step core + exchange-scheme registry (PR 4 acceptance).

Pins: (a) the refactor is invisible — ``simulate_distributed(...,
emulate=True)`` is bit-identical to a plain partitioned reference run
inside the test (the historical drive and PRNG layout, delivery straight
from the DCSR table, the event scheme's capacity contract); (b) the sharded ``blocked`` scheme
is count-parity with ``event``;
(c) the distributed path has full observability parity with the
monolithic one (probe records, trials batching), and pad neurons never
leak into any record or count; (d) the capacity knobs and legacy
observability aliases are deprecated-but-working shims.
"""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import given, requires_hypothesis, settings, st
from repro.core import (CapacityConfig, SimConfig, available_schemes,
                        get_scheme, simulate, synthetic_flywire)
from repro.core.dcsr import build_dcsr
from repro.core.distributed import DistConfig, simulate_distributed
from repro.core.exchange import build_dist_arrays
from repro.core.neuron import init_state, lif_step, lif_step_fx
from repro.core.partition import even_partition
from repro.exp import (Compose, ProbeSpec, StepCurrent, per_neuron,
                       run_dist_trials)


@pytest.fixture(scope="module")
def setup():
    c = synthetic_flywire(n=1600, target_synapses=48_000, seed=8)
    sugar = np.arange(20)
    d = build_dcsr(c, even_partition(c, 4))
    return c, sugar, d


# --------------------------------------------------------------------------
# Registry + pinned pre-refactor bit-identity
# --------------------------------------------------------------------------

def test_exchange_registry():
    assert {"local", "bitmap", "event", "blocked"} <= set(available_schemes())
    assert get_scheme("event").name == "event"
    with pytest.raises(ValueError, match="unknown exchange scheme"):
        get_scheme("no-such-scheme")
    # the monolithic-only scheme is rejected on the distributed entry point
    c = synthetic_flywire(n=300, target_synapses=3_000, seed=0)
    d = build_dcsr(c, even_partition(c, 2))
    with pytest.raises(ValueError, match="unknown distributed"):
        simulate_distributed(d, DistConfig(sim=SimConfig(), scheme="local"),
                             5, emulate=True)


def _ref_dist_run(d, sim, t_steps, sugar, seed, cap=None):
    """Plain reference of the partitioned step, written without the
    exchange layer: per-partition PRNG streams and drive exactly as the
    historical distributed step drew them, one global spike vector, and
    delivery straight from the DCSR synapse table.

    ``cap`` (a CapacityConfig) models the event scheme's bounded exchange
    as its contract states it: per partition the first ``block_capacity``
    active 128-blocks, then the first ``spike_capacity`` active neurons in
    them; per target partition the first ``syn_budget`` synapses of the
    kept events in global-id order, each source's synapses in table
    order.  Drops are requested minus delivered synapses.  ``cap=None``
    delivers everything (the bitmap scheme).  Returns (counts in original
    ids, dropped)."""
    P_, U = d.n_parts, d.part_size
    n_glob = P_ * U
    p = sim.params
    real = jnp.asarray(d.inv_perm.reshape(P_, U) >= 0)
    m = np.zeros(d.n_orig, bool)
    if sugar is not None:
        m[np.asarray(sugar)] = True
    inv = np.where(d.inv_perm >= 0, d.inv_perm, 0)
    sugar_mask = jnp.asarray((m[inv] & (d.inv_perm >= 0)).reshape(P_, U))

    # static synapse tables: fan-out per (target partition, source) and the
    # offset of each synapse within its source's run
    valid = d.syn_src < n_glob
    src = np.where(valid, d.syn_src, 0)
    fo = np.stack([np.bincount(src[q][valid[q]], minlength=n_glob)
                   for q in range(P_)])                       # [P, n_glob]
    off = np.zeros_like(src)
    for q in range(P_):
        order = np.argsort(np.where(valid[q], src[q], n_glob), kind="stable")
        ss = src[q][order]
        first = np.r_[0, np.flatnonzero(np.diff(ss)) + 1]
        run = np.repeat(first, np.diff(np.r_[first, len(ss)]))
        off[q, order] = np.arange(len(ss)) - run
    gfo = jnp.asarray(fo.sum(axis=0))
    fo, off, src = jnp.asarray(fo), jnp.asarray(off), jnp.asarray(src)
    valid, tgt = jnp.asarray(valid), jnp.asarray(d.syn_tgt_local)
    w = jnp.asarray(d.syn_w)
    q_of = jnp.broadcast_to(jnp.arange(P_)[:, None], src.shape)

    def kept_spikes(delayed):                    # [P, U] -> [P, U] bool
        if cap is None:
            return delayed
        nb = -(-U // 128)
        bcap = cap.block_capacity or max(1, min(nb, cap.spike_capacity))
        blocks = jnp.pad(delayed, ((0, 0), (0, nb * 128 - U))).reshape(
            P_, nb, 128).any(axis=2)
        blk_ok = blocks & (jnp.cumsum(blocks, axis=1) <= bcap)
        elig = delayed & jnp.repeat(blk_ok, 128, axis=1)[:, :U]
        return elig & (jnp.cumsum(elig, axis=1) <= cap.spike_capacity)

    def step(carry, _):
        lif, ring, ptr, keys, counts, dropped = carry
        ks = jax.vmap(lambda k: jax.random.split(k, 3))(keys)
        delayed = ring[ptr]
        kept = kept_spikes(delayed).reshape(-1)
        run_fo = fo * kept[None, :]
        before = jnp.cumsum(run_fo, axis=1) - run_fo
        ok = valid & kept[src] & (
            cap is None or
            jnp.take_along_axis(before, src, axis=1) + off < cap.syn_budget)
        g = jnp.zeros((P_, U + 1), jnp.float32).at[q_of, tgt].add(
            jnp.where(ok, w, 0.0))[:, :U]
        delivered = jnp.sum(ok)
        dropped = dropped + jnp.sum(delayed.reshape(-1) * gfo) - delivered

        bern = jax.vmap(lambda k, r: jax.random.bernoulli(k, r, (U,)),
                        in_axes=(0, None))
        v_mv = force = None
        if sim.poisson_rate_hz > 0:
            draws = bern(ks[:, 1], sim.poisson_rate_hz * p.dt * 1e-3)
            draws = (draws & sugar_mask).astype(jnp.float32)
            if sim.poisson_to_v:
                v_mv = draws * (1.5 * p.v_th)
            else:
                g = g + draws * sim.poisson_weight
        if sim.background_rate_hz > 0:
            force = bern(ks[:, 2], sim.background_rate_hz * p.dt * 1e-3) & real
        if sim.fixed_point:
            v_fx = None if v_mv is None else jnp.round(
                v_mv / p.w_scale).astype(jnp.int32)
            lif, spikes = lif_step_fx(lif, jnp.round(g).astype(jnp.int32), p,
                                      v_fx, force)
        else:
            lif, spikes = lif_step(lif, g * p.w_scale, p, v_mv, force)
        spikes = spikes & real
        return (lif, ring.at[ptr].set(spikes), (ptr + 1) % p.delay_steps,
                ks[:, 0], counts + spikes, dropped), None

    lif0 = jax.tree.map(lambda x: x.reshape(P_, U),
                        init_state(n_glob, p, sim.fixed_point))
    carry = (lif0, jnp.zeros((p.delay_steps, P_, U), bool), jnp.int32(0),
             jax.random.split(jax.random.PRNGKey(seed), P_),
             jnp.zeros((P_, U), jnp.int32), jnp.int32(0))
    carry, _ = jax.jit(lambda c: jax.lax.scan(step, c, None,
                                              length=t_steps))(carry)
    counts = np.asarray(carry[4]).reshape(-1)
    out = np.zeros(d.n_orig, np.int64)
    out[d.inv_perm[d.inv_perm >= 0]] = counts[d.inv_perm >= 0]
    return out, int(carry[5])


@pytest.mark.parametrize("scheme", ["bitmap", "event"])
@pytest.mark.parametrize("fx", [False, True])
def test_emulated_distributed_bit_identical_to_pre_refactor(setup, scheme, fx):
    """The unified step core under each exchange scheme returns exactly
    the counts and drops of the plain partitioned reference on the legacy
    scenario (n=1600, P=4, sugar Poisson drive, T=300)."""
    c, sugar, d = setup
    sim = SimConfig(engine="csr", fixed_point=fx, poisson_to_v=not fx,
                    quantize_bits=9 if fx else None)
    dcfg = DistConfig(sim=sim, scheme=scheme)
    r = simulate_distributed(d, dcfg, 300, sugar, seed=3, emulate=True)
    d_ref = build_dcsr(c, even_partition(c, 4),
                       quantize_bits=sim.quantize_bits)
    want, want_drop = _ref_dist_run(
        d_ref, sim, 300, sugar, 3,
        cap=dcfg.capacity if scheme == "event" else None)
    assert want.sum() > 0
    np.testing.assert_array_equal(r.counts, want)
    assert r.dropped == want_drop


@pytest.mark.parametrize("cap", [CapacityConfig(4, 256, 0),
                                 CapacityConfig(64, 96, 2)],
                         ids=["spike_capacity", "syn_budget"])
def test_overflow_drops_bit_identical_to_pre_refactor(setup, cap):
    """Same check under capacity starvation (of the kept spikes, then of
    the synapse slots): the event scheme's bounded exchange keeps and
    drops exactly what its contract says, and counts every lost
    synapse."""
    c, sugar, d = setup
    sim = SimConfig(engine="csr", background_rate_hz=300.0)
    r = simulate_distributed(d, DistConfig(sim=sim, scheme="event",
                                           capacity=cap),
                             50, sugar, seed=0, emulate=True)
    want, want_drop = _ref_dist_run(d, sim, 50, sugar, 0, cap=cap)
    assert want_drop > 0                       # deliberately starved
    np.testing.assert_array_equal(r.counts, want)
    assert r.dropped == want_drop


# --------------------------------------------------------------------------
# Sharded blocked scheme
# --------------------------------------------------------------------------

def test_blocked_scheme_count_parity_with_event(setup):
    """The ROADMAP item's acceptance: tile-granular delivery over the
    per-partition blk_id remap is a storage change, not an approximation —
    counts are bit-identical to the event scheme (integer weights sum
    exactly in f32)."""
    c, sugar, d = setup
    sim = SimConfig(engine="csr")
    e = simulate_distributed(d, DistConfig(sim=sim, scheme="event"), 200,
                             sugar, seed=3, emulate=True)
    b = simulate_distributed(d, DistConfig(sim=sim, scheme="blocked"), 200,
                             sugar, seed=3, emulate=True)
    np.testing.assert_array_equal(e.counts, b.counts)
    assert b.dropped == 0


def test_blocked_scheme_tile_stats_track_sparsity(setup):
    """tiles_live/tiles_skipped counters: conserved per step (live +
    skipped == stored), and sparser activity skips more tiles."""
    from repro.kernels.spike_prop.ops import build_blocked_sharded
    c, sugar, d = setup
    stored = build_blocked_sharded(d).tiles_stored
    T = 100

    def run(background_hz):
        sim = SimConfig(engine="csr", poisson_rate_hz=0.0,
                        background_rate_hz=background_hz)
        return simulate_distributed(
            d, DistConfig(sim=sim, scheme="blocked"), T, None, seed=0,
            emulate=True)

    quiet, busy = run(2.0), run(80.0)
    for r in (quiet, busy):
        assert int(r.stats["tiles_live"] + r.stats["tiles_skipped"]) \
            == stored * T
    assert int(quiet.stats["tiles_live"]) < int(busy.stats["tiles_live"])


def test_blocked_scheme_quantized_matches_bitmap(setup):
    """Weights quantized by build_dcsr flow identically through the dense
    tiles and the flat in-CSR."""
    c, sugar, _ = setup
    d9 = build_dcsr(c, even_partition(c, 4), quantize_bits=9)
    sim = SimConfig(engine="csr", quantize_bits=9, fixed_point=True,
                    poisson_to_v=False)
    a = simulate_distributed(d9, DistConfig(sim=sim, scheme="bitmap"), 150,
                             sugar, seed=5, emulate=True)
    b = simulate_distributed(d9, DistConfig(sim=sim, scheme="blocked"), 150,
                             sugar, seed=5, emulate=True)
    np.testing.assert_array_equal(a.counts, b.counts)


# --------------------------------------------------------------------------
# Distributed observability parity (satellite: probe records)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("fx", [False, True])
@pytest.mark.parametrize("scheme", ["bitmap", "event", "blocked"])
def test_probe_record_parity_monolithic_vs_distributed(setup, scheme, fx):
    """Under a deterministic stimulus the network evolution is identical,
    so every probe record must match the monolithic run after the
    inv_perm mapping: raster and voltage bit-exactly, pop-rate to float
    tolerance, drops exactly."""
    c, _, d = setup
    ids = (3, 100, 777, 1599)
    stim = Compose((StepCurrent(weights=per_neuron(np.arange(40), 90.0, c.n),
                                t_on=5, t_off=60),))
    probes = ProbeSpec(raster=True, voltage=ids, pop_rate=True, drops=True)
    cfg = SimConfig(engine="csr", fixed_point=fx,
                    quantize_bits=9 if fx else None)
    T = 80
    mono = simulate(c, cfg, T, stimulus=stim, probes=probes, seed=0)
    dist = simulate_distributed(d, DistConfig(sim=cfg, scheme=scheme), T,
                                stimulus=stim, probes=probes, seed=0,
                                emulate=True)
    assert int(np.asarray(mono.counts).sum()) > 0
    np.testing.assert_array_equal(np.asarray(mono.counts), dist.counts)
    np.testing.assert_array_equal(np.asarray(mono.raster), dist.raster)
    np.testing.assert_array_equal(np.asarray(mono.records["v"]),
                                  dist.records["v"])
    np.testing.assert_allclose(np.asarray(mono.records["pop_rate_hz"]),
                               dist.records["pop_rate_hz"], rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(mono.records["dropped"]),
                                  dist.records["dropped"])
    # full SimResult shape: final LIF state mapped back per neuron
    np.testing.assert_array_equal(np.asarray(mono.state.v),
                                  np.asarray(dist.state.v))


def test_dist_voltage_probe_out_of_range(setup):
    c, _, d = setup
    with pytest.raises(ValueError, match="out of range"):
        simulate_distributed(d, DistConfig(sim=SimConfig(engine="csr")), 5,
                             emulate=True,
                             probes=ProbeSpec(voltage=(c.n,)))


def test_dist_trials_match_sequential(setup):
    """run_dist_trials == the same seeds run one by one (emulated)."""
    c, sugar, d = setup
    cfg = DistConfig(sim=SimConfig(engine="csr", background_rate_hz=10.0),
                     scheme="event")
    seeds = [3, 11, 42]
    batch = run_dist_trials(d, cfg, 120, sugar, seeds=seeds, emulate=True,
                            probes=ProbeSpec(raster=True))
    assert batch.counts.shape == (3, c.n)
    assert batch.records["raster"].shape == (3, 120, c.n)
    for i, s in enumerate(seeds):
        one = simulate_distributed(d, cfg, 120, sugar, seed=s, emulate=True)
        np.testing.assert_array_equal(batch.counts[i], one.counts)
        assert int(batch.dropped[i]) == one.dropped
        np.testing.assert_array_equal(batch.state.v[i],
                                      np.asarray(one.state.v))
    np.testing.assert_array_equal(
        batch.records["raster"].sum(axis=1), batch.counts)


# --------------------------------------------------------------------------
# Pad-neuron property (satellite: distributed observability tests)
# --------------------------------------------------------------------------

@requires_hypothesis
@settings(max_examples=6, deadline=None)
@given(n=st.sampled_from([301, 640, 1100]), n_parts=st.sampled_from([2, 3, 5]),
       seed=st.integers(min_value=0, max_value=3))
def test_pad_neurons_never_in_any_record_or_count(n, n_parts, seed):
    """Property: whatever the (n, P, seed) geometry — including partition
    sizes that force heavy padding — pad slots never spike, never count,
    and never reach any probe record."""
    from repro.core.distributed import _run_partitioned
    import jax
    c = synthetic_flywire(n=n, target_synapses=6 * n, seed=seed)
    d = build_dcsr(c, even_partition(c, n_parts))
    cfg = DistConfig(sim=SimConfig(engine="csr", background_rate_hz=200.0),
                     scheme="event")
    keys = jax.random.split(jax.random.PRNGKey(seed), d.n_parts)
    out, records, _probes, _owner = _run_partitioned(
        d, cfg, 25, keys, None, None, ProbeSpec(raster=True), None,
        emulate=True, trials=False)
    pad = d.inv_perm.reshape(d.n_parts, d.part_size) < 0
    counts = np.asarray(out.counts)              # [P, U]
    raster = np.asarray(records["raster"])       # [P, T, U]
    assert counts.sum() > 0                      # the drive elicits spikes
    assert counts[pad].sum() == 0
    assert not raster.transpose(0, 2, 1)[pad].any()
    # and the mapped-back result carries every real spike, none invented
    res = simulate_distributed(d, cfg, 25, None, seed=seed, emulate=True,
                               probes=ProbeSpec(raster=True))
    assert res.counts.sum() == counts.sum()
    assert res.raster.sum() == raster.sum()


# --------------------------------------------------------------------------
# build_dist_arrays: vectorized + memoized (satellite)
# --------------------------------------------------------------------------

def _ref_dist_arrays(d):
    """The pre-vectorization per-partition loop, kept as the oracle."""
    P_, U, S = d.n_parts, d.part_size, d.s_max
    n_glob = P_ * U
    out_indptr = np.zeros((P_, n_glob + 1), dtype=np.int32)
    out_tgt = np.full((P_, S), U, dtype=np.int32)
    out_w = np.zeros((P_, S), dtype=np.float32)
    for p in range(P_):
        valid = d.syn_src[p] < n_glob
        src = d.syn_src[p][valid]
        order = np.argsort(src, kind="stable")
        m = len(src)
        out_tgt[p, :m] = d.syn_tgt_local[p][valid][order]
        out_w[p, :m] = d.syn_w[p][valid][order]
        counts = np.bincount(src[order], minlength=n_glob)
        np.cumsum(counts, out=out_indptr[p, 1:])
    gfo = np.diff(out_indptr, axis=1).sum(axis=0).astype(np.int32)
    return out_indptr, out_tgt, out_w, gfo.reshape(P_, U)


def test_build_dist_arrays_matches_reference_loop(setup):
    c, _, d = setup
    arrs = build_dist_arrays(d)
    indptr, tgt, w, gfo = _ref_dist_arrays(d)
    np.testing.assert_array_equal(np.asarray(arrs.out_indptr), indptr)
    np.testing.assert_array_equal(np.asarray(arrs.out_tgt), tgt)
    np.testing.assert_array_equal(np.asarray(arrs.out_w), w)
    np.testing.assert_array_equal(np.asarray(arrs.src_gfo), gfo)
    np.testing.assert_array_equal(
        np.asarray(arrs.pad_mask), d.inv_perm.reshape(d.n_parts, -1) >= 0)


def test_build_dist_arrays_memoized_on_dcsr(setup):
    c, _, d = setup
    assert build_dist_arrays(d) is build_dist_arrays(d)
    # a different snapshot gets its own entry
    d2 = build_dcsr(c, even_partition(c, 2))
    assert build_dist_arrays(d2) is not build_dist_arrays(d)


# --------------------------------------------------------------------------
# Capacity dedup + deprecation shims (satellites)
# --------------------------------------------------------------------------

def test_capacity_config_routes_both_configs():
    cap = CapacityConfig(spike_capacity=33, syn_budget=4444,
                         block_capacity=7)
    sim = SimConfig(engine="event", **cap.as_config_kwargs())
    assert sim.capacity == cap
    # replace() with a new capacity must take effect (no stale-mirror
    # clobber) and stay silent
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        swapped = dataclasses.replace(
            sim, capacity=CapacityConfig(spike_capacity=1024))
    assert swapped.capacity.spike_capacity == 1024
    dc = DistConfig(sim=sim, capacity=cap)
    assert dc.capacity == cap
    # historical defaults preserved per config
    assert SimConfig().capacity == CapacityConfig(512, 65_536, 0)
    assert DistConfig(sim=SimConfig()).capacity == CapacityConfig(256, 32_768, 0)


def test_legacy_capacity_fields_warn_and_still_work():
    with pytest.warns(DeprecationWarning, match="syn_budget"):
        cfg = SimConfig(engine="event", syn_budget=256)
    assert cfg.capacity.syn_budget == 256
    assert cfg.capacity.spike_capacity == 512     # untouched default
    with pytest.warns(DeprecationWarning, match="spike_capacity"):
        dc = DistConfig(sim=SimConfig(), spike_capacity=4, syn_budget=99)
    assert (dc.capacity.spike_capacity, dc.capacity.syn_budget) == (4, 99)
    # replace() round-trips silently (the shims are consumed at init)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        cfg2 = dataclasses.replace(cfg, background_rate_hz=5.0)
        # and an explicitly replaced capacity wins even on a config that
        # was originally built through a legacy shim
        cfg3 = dataclasses.replace(
            cfg, capacity=CapacityConfig(syn_budget=9999))
    assert cfg2.capacity == cfg.capacity
    assert cfg3.capacity.syn_budget == 9999


def test_legacy_observability_aliases_warn():
    c = synthetic_flywire(n=300, target_synapses=3_000, seed=1)
    with pytest.warns(DeprecationWarning, match="collect_raster"):
        cfg = SimConfig(engine="csr", collect_raster=True)
    with pytest.warns(DeprecationWarning, match="sugar_neurons"):
        simulate(c, SimConfig(engine="csr"), 5, np.arange(5))
    # the aliases still behave
    res = simulate(c, cfg, 5, stimulus=Compose(()))
    assert res.raster is not None and res.raster.shape == (5, c.n)
