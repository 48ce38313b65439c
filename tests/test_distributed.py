"""Distributed multi-core simulator (paper §3.2.2-3.2.3): emulated vmap
semantics in-process + real shard_map in a multi-device subprocess."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import SimConfig, parity, simulate, synthetic_flywire
from repro.core.dcsr import build_dcsr
from repro.core.distributed import DistConfig, simulate_distributed
from repro.core.partition import even_partition


@pytest.fixture(scope="module")
def setup():
    c = synthetic_flywire(n=1600, target_synapses=48_000, seed=8)
    sugar = np.arange(20)
    p = even_partition(c, 4)
    d = build_dcsr(c, p)
    return c, sugar, d


def test_bitmap_equals_event_scheme(setup):
    """The two comm schemes deliver identical spikes given the same RNG —
    they differ only in message format (paper's SSD vs SAR framing)."""
    c, sugar, d = setup
    sim = SimConfig(engine="csr")
    rb = simulate_distributed(d, DistConfig(sim=sim, scheme="bitmap"), 300,
                              sugar, seed=3, emulate=True)
    re_ = simulate_distributed(d, DistConfig(sim=sim, scheme="event"), 300,
                               sugar, seed=3, emulate=True)
    np.testing.assert_array_equal(rb.counts, re_.counts)
    assert re_.dropped == 0


def test_distributed_parity_with_single_device(setup):
    """Spike-rate parity across implementations — the paper's validation
    statistic (Fig 6/12), applied distributed-vs-monolithic."""
    c, sugar, d = setup
    sim = SimConfig(engine="csr")
    T, trials = 400, 3
    rs = [np.asarray(simulate(c, sim, T, sugar, seed=s).counts)
          for s in range(trials)]
    rd = [simulate_distributed(d, DistConfig(sim=sim, scheme="event"), T,
                               sugar, seed=50 + s, emulate=True).counts
          for s in range(trials)]
    ra = np.stack(rs).mean(0) / (T * 0.1e-3)
    rb = np.stack(rd).mean(0) / (T * 0.1e-3)
    st = parity(ra, rb, active_thresh_hz=1.0)
    assert st.pearson_r > 0.8, st.summary()


def test_event_capacity_drop_accounting(setup):
    c, sugar, d = setup
    sim = SimConfig(engine="csr", background_rate_hz=300.0)
    r = simulate_distributed(
        d, DistConfig(sim=sim, scheme="event", spike_capacity=4,
                      syn_budget=256), 50, sugar, seed=0, emulate=True)
    assert r.dropped > 0


def test_distributed_event_overflow_exact_vs_numpy(setup):
    """Single-step overflow contract for the sharded event path: per
    partition, the delivered subset must agree with the flat local store on
    every non-dropped synapse, and the summed drop count (budget overruns +
    the global fan-out of spikes beyond the event capacity) must match a
    numpy reference exactly."""
    from repro.core.compaction import derived_block_capacity, two_level_active
    from repro.core.exchange import build_dist_arrays
    from repro.core.exchange.event import deliver_events
    from test_compaction import np_two_level

    c, _, d = setup
    P_, U = d.n_parts, d.part_size
    n_glob = P_ * U
    arrs = build_dist_arrays(d)
    indptr = np.asarray(arrs.out_indptr)
    out_tgt, out_w = np.asarray(arrs.out_tgt), np.asarray(arrs.out_w)
    gfo = np.asarray(arrs.src_gfo)

    rng = np.random.default_rng(5)
    delayed = rng.random((P_, U)) < 0.05
    delayed &= np.asarray(arrs.pad_mask)

    for cap, budget in [(4, 64), (16, 300), (256, 32_768)]:
        bcap = derived_block_capacity(U, cap)
        # per-partition compaction -> the all-gathered global event list
        gids = []
        for p in range(P_):
            idx = np.asarray(two_level_active(delayed[p], cap, bcap))
            np.testing.assert_array_equal(
                idx, np_two_level(delayed[p], cap, bcap))
            gids.append(np.where(idx < U, idx + p * U, n_glob))
        events = np.concatenate(gids).astype(np.int32)

        total_drop = 0
        for p in range(P_):
            g, bdrop, _ = deliver_events(
                events, arrs.out_indptr[p], arrs.out_tgt[p], arrs.out_w[p],
                U, n_glob, budget)
            flat = np.concatenate(
                [np.arange(indptr[p][e], indptr[p][e + 1])
                 for e in events if e < n_glob] or [np.array([], int)])
            g_ref = np.zeros(U + 1, np.float64)
            np.add.at(g_ref, out_tgt[p][flat[:budget]],
                      out_w[p][flat[:budget]])
            np.testing.assert_array_equal(np.asarray(g), g_ref[:U])
            assert int(bdrop) == max(len(flat) - budget, 0)
            kept = np.asarray(gids[p])
            kept = kept[kept < n_glob] - p * U
            over_fo = int(gfo[p][delayed[p]].sum()) - int(gfo[p][kept].sum())
            total_drop += max(len(flat) - budget, 0) + over_fo

        # numpy ground truth: requested global fan-out of every delayed
        # spike minus what the event lists + budgets actually delivered
        requested = int(gfo[delayed].sum())
        delivered = 0
        for p in range(P_):
            tot = sum(int(indptr[p][e + 1] - indptr[p][e])
                      for e in events if e < n_glob)
            delivered += min(tot, budget)
        assert total_drop == requested - delivered
    assert total_drop == 0   # the generous provisioning dropped nothing


SHARD_MAP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, "src")
    import numpy as np
    from repro.core import SimConfig, synthetic_flywire
    from repro.core.dcsr import build_dcsr
    from repro.core.distributed import DistConfig, simulate_distributed
    from repro.core.partition import even_partition

    c = synthetic_flywire(n=1600, target_synapses=48_000, seed=8)
    sugar = np.arange(20)
    d = build_dcsr(c, even_partition(c, 4))
    sim = SimConfig(engine="csr")
    for scheme in ("bitmap", "event", "blocked"):
        cfg = DistConfig(sim=sim, scheme=scheme)
        emu = simulate_distributed(d, cfg, 200, sugar, seed=3, emulate=True)
        real = simulate_distributed(d, cfg, 200, sugar, seed=3, emulate=False)
        assert (emu.counts == real.counts).all(), scheme
        assert emu.stats.keys() == real.stats.keys()
        print(scheme, "ok", int(real.counts.sum()))

    # trial batching under real shard_map matches sequential runs
    from repro.exp import run_dist_trials
    cfg = DistConfig(sim=sim, scheme="event")
    tr = run_dist_trials(d, cfg, 100, sugar, seeds=[3, 11], emulate=False)
    for i, s in enumerate((3, 11)):
        one = simulate_distributed(d, cfg, 100, sugar, seed=s, emulate=False)
        assert (tr.counts[i] == one.counts).all()
    print("trials ok", int(tr.counts.sum()))
""")


def test_shard_map_matches_emulation(tmp_path):
    """The real shard_map execution on 4 host devices is bit-identical to
    the vmap emulation, for every exchange scheme."""
    script = tmp_path / "run_shard_map.py"
    script.write_text(SHARD_MAP_SCRIPT)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True, timeout=600, env=env,
                         cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert out.returncode == 0, out.stderr[-2000:]
    for tag in ("bitmap ok", "event ok", "blocked ok", "trials ok"):
        assert tag in out.stdout, out.stdout
