"""Run the simulator's main path once on a TPU and check what comes out.

    python chip_smoke.py               # one chip: every single-chip phase
    python chip_smoke.py --four-chips  # only the partitioned run, 4 chips

One process drives the chip (a second process could not reach it).  Each
phase prints one line with its checks and its wall time; the wall times
include compilation and are smoke timings, not benchmark numbers.  Any
failed check raises, so the script exits non-zero; nothing is caught and
skipped.  The last line of standard output is one JSON object:

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

Without a TPU the script stops at the device check with a non-zero exit
and prints no result: it never falls back to the CPU.

Phases on one chip:

1. paper scale: ``FlyWireConfig`` (139,255 neurons, 15M synapses, event
   engine, Q19.12, dt = 0.1 ms) under ``sugar_feeding`` for 1,000 steps,
   then a 4-seed ``run_trials`` batch.  The run must be lossless, finite,
   and the sugar neurons must fire;
2. TPU against CPU at ``SMOKE`` size in this process: Q19.12 counts, state
   and drops are bit-identical (integer arithmetic and threefry bits do
   not depend on the backend); the float32 event path is at rate parity
   with the float ``csr`` reference;
3. the compiled Pallas tile path: ``blocked_fused`` at bench width (20,000
   neurons, 600k synapses) for 200 steps; Q19.12 counts equal the ``csr``
   engine's, float32 counts are at parity with them;
4. serving: 4 ``SimRequest`` through ``SimServer`` at bench width, each
   bit-equal to its solo ``simulate()`` run.

``--four-chips`` runs only ``simulate_distributed`` at paper scale over a
4-device mesh (event scheme) and the same partitioning emulated on one
device; counts and drops must be bit-identical, and the partitions must
sit on 4 distinct chips.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

PAPER_STEPS = 1_000          # 100 ms of biology at dt = 0.1 ms
PAPER_TRIALS = 4
BENCH_N, BENCH_SYN = 20_000, 600_000   # launch/simulate.py --scale bench
TILE_STEPS = 200
TILE_BACKGROUND_HZ = 5.0     # wakes source blocks beyond the sugar drive
PARITY_TRIALS = 10           # the paper's trial-averaged rate statistic
PARITY_MIN_R = 0.8           # as tests/test_distributed.py holds parity
SERVE_REQUESTS, SERVE_STEPS, SERVE_CHUNK = 4, 500, 250


def check(ok, what: str) -> None:
    if not ok:
        raise AssertionError(f"check failed: {what}")


def report(phase: str, t0: float, lines: list[str]) -> None:
    print(f"[chip_smoke] {phase} ({time.monotonic() - t0:.1f} s wall, "
          f"smoke timing): " + "; ".join(lines), flush=True)


def tpu_devices(need: int):
    """The devices JAX found; exits unless they are ``need`` or more TPUs."""
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"[chip_smoke] devices: platform={d.platform} "
          f"kind={d.device_kind} count={len(devs)}", flush=True)
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform {d.platform!r}); "
                 f"this script never runs on the CPU")
    if len(devs) < need:
        sys.exit(f"chip_smoke: need {need} TPU devices, found {len(devs)}")
    return devs


def sugar_stimulus(c, fw, cfg, **extra):
    """``sugar_feeding`` with the population size and rate of ``fw``."""
    from repro.exp import build_scenario
    return build_scenario("sugar_feeding", c, cfg, n_sugar=fw.n_sugar,
                          rate_hz=fw.sugar_rate_hz, **extra)


def same(a, b) -> bool:
    import numpy as np
    return np.array_equal(np.asarray(a), np.asarray(b))


def same_run(a, b) -> bool:
    """Counts, every LIF state leaf and the drop total are bit-equal."""
    import numpy as np
    return (same(a.counts, b.counts)
            and all(same(x, y) for x, y in zip(a.state, b.state))
            and int(np.asarray(a.dropped).sum())
            == int(np.asarray(b.dropped).sum()))


def paper_connectome(fw):
    from repro.core import synthetic_flywire
    return synthetic_flywire(n=fw.n_neurons,
                             target_synapses=fw.target_synapses, seed=0)


# --------------------------------------------------------------------------
# Phases
# --------------------------------------------------------------------------

def phase_paper_scale(fw, steps: int, trials: int) -> None:
    import numpy as np

    from repro.core import build_synapses, simulate
    from repro.exp import run_trials
    t0 = time.monotonic()
    c = paper_connectome(fw)
    gen_s = time.monotonic() - t0
    cfg = fw.sim
    stim = sugar_stimulus(c, fw, cfg)
    sugar = np.asarray(stim.parts[0].idx)
    syn = build_synapses(c, cfg)
    t1 = time.monotonic()
    r = simulate(c, cfg, steps, stimulus=stim, seed=0, syn=syn)
    counts = np.asarray(r.counts)
    run_s = time.monotonic() - t1
    check(int(r.dropped) == 0, f"lossless run, dropped={int(r.dropped)}")
    check(all(np.isfinite(np.asarray(x, np.float64)).all() for x in r.state),
          "finite LIF state")
    check((counts >= 0).all(), "non-negative counts")
    check(counts[sugar].sum() > 0, "sugar neurons fire")
    t2 = time.monotonic()
    tr = run_trials(c, cfg, steps, stimulus=stim, seeds=trials, syn=syn)
    tcounts = np.asarray(tr.counts)
    trials_s = time.monotonic() - t2
    check((np.asarray(tr.dropped) == 0).all(),
          f"lossless trials, dropped={np.asarray(tr.dropped).tolist()}")
    check((tcounts[:, sugar].sum(axis=1) > 0).all(),
          "sugar neurons fire in every trial")
    check(same(tcounts[0], counts), "trial seed 0 == the single run")
    report("paper-scale event Q19.12", t0, [
        f"n={c.n} synapses={c.nnz} steps={steps}",
        f"generate {gen_s:.1f} s, simulate {run_s:.1f} s, "
        f"{trials} trials {trials_s:.1f} s",
        f"dropped=0 spikes={int(counts.sum())} "
        f"sugar_spikes={int(counts[sugar].sum())} "
        f"active={int((counts > 0).sum())}",
        f"trial spikes={tcounts.sum(axis=1).tolist()} dropped=0, "
        f"seed 0 bit-equal to the single run"])


def phase_tpu_vs_cpu(fw, parity_trials: int) -> None:
    import jax
    import numpy as np

    from repro.core import SimConfig, parity, simulate, synthetic_flywire
    from repro.exp import run_trials
    t0 = time.monotonic()
    c = synthetic_flywire(n=fw.n_neurons,
                          target_synapses=fw.target_synapses, seed=0)
    cfg, steps = fw.sim, fw.t_steps
    tpu = simulate(c, cfg, steps, stimulus=sugar_stimulus(c, fw, cfg), seed=0)
    cpu_dev = jax.devices("cpu")[0]
    with jax.default_device(cpu_dev):
        cpu = simulate(c, cfg, steps, stimulus=sugar_stimulus(c, fw, cfg),
                       seed=0)
    check(tpu.counts.devices() == {jax.devices()[0]}, "chip run on the chip")
    check(cpu.counts.devices() == {cpu_dev}, "CPU run on the CPU")
    check(int(np.asarray(tpu.counts).sum()) > 0, "the network spikes")
    check(same_run(tpu, cpu), "Q19.12 TPU == CPU (counts, state, dropped)")

    # float32 event path against the float csr reference, the launcher's
    # --parity statistic: Brian2 drive on raw weights vs the Loihi drive on
    # 9-bit weights, trial-averaged over disjoint seeds
    f32 = dataclasses.replace(cfg, fixed_point=False)
    ref_cfg = SimConfig(engine="csr", params=cfg.params, poisson_to_v=True)
    dt = cfg.params.dt
    ra = run_trials(c, ref_cfg, steps,
                    stimulus=sugar_stimulus(c, fw, ref_cfg),
                    seeds=[10 + i for i in range(parity_trials)]
                    ).mean_rates_hz(steps, dt)
    ev = run_trials(c, f32, steps, stimulus=sugar_stimulus(c, fw, f32),
                    seeds=[20 + i for i in range(parity_trials)])
    st = parity(ra, ev.mean_rates_hz(steps, dt))
    check(st.n_active > 0 and st.n_nonfinite == 0, "parity has active rates")
    check(st.pearson_r > PARITY_MIN_R,
          f"f32 event parity r={st.pearson_r:.4f} > {PARITY_MIN_R}")
    check((np.asarray(ev.dropped) == 0).all(), "f32 event trials lossless")
    # same seeds and drive on csr: integer weights sum exactly, so equality
    # is expected; reported, parity is the check
    csr32 = dataclasses.replace(f32, engine="csr")
    cs = run_trials(c, csr32, steps, stimulus=sugar_stimulus(c, fw, csr32),
                    seeds=[20 + i for i in range(parity_trials)])
    report("TPU vs CPU at SMOKE size", t0, [
        f"n={c.n} steps={steps} spikes={int(np.asarray(tpu.counts).sum())} "
        f"dropped={int(tpu.dropped)}",
        "Q19.12 counts/state/dropped bit-identical TPU == CPU",
        f"f32 event vs float csr reference ({parity_trials} trials): "
        f"{st.summary()}",
        f"f32 event == f32 csr on the same seeds: "
        f"{same(ev.counts, cs.counts)}"])


def phase_tile_path(c, fw) -> None:
    import numpy as np

    from repro.core import build_synapses, parity, simulate
    t0 = time.monotonic()
    # both precisions share the 9-bit weights, so build each store once
    fused = dataclasses.replace(fw.sim, engine="blocked_fused")
    tiles = build_synapses(c, fused)
    csr = build_synapses(c, dataclasses.replace(fused, engine="csr"))
    check(tiles.interpret is False, "tile kernel compiled, not interpreted")
    lines = [f"interpret={tiles.interpret} tiles={tiles.tiles_stored} "
             f"occupancy={tiles.occupancy:.2e}"]
    for fx in (True, False):
        cfg = dataclasses.replace(fused, fixed_point=fx)
        ref_cfg = dataclasses.replace(cfg, engine="csr")
        stim = sugar_stimulus(c, fw, cfg, background_hz=TILE_BACKGROUND_HZ)
        out = simulate(c, cfg, TILE_STEPS, stimulus=stim, seed=0, syn=tiles)
        ref = simulate(c, ref_cfg, TILE_STEPS, stimulus=stim, seed=0, syn=csr)
        a, b = np.asarray(out.counts), np.asarray(ref.counts)
        check(a.sum() > 0, "the tile path spikes")
        check(int(out.dropped) == 0 and int(ref.dropped) == 0, "lossless")
        exact = same(a, b)
        if fx:
            check(exact, "Q19.12 blocked_fused counts == csr counts")
            lines.append(f"Q19.12: spikes={int(a.sum())} == csr")
        else:
            dt = cfg.params.dt
            st = parity(a / (TILE_STEPS * dt * 1e-3),
                        b / (TILE_STEPS * dt * 1e-3))
            check(st.pearson_r > PARITY_MIN_R,
                  f"f32 blocked_fused parity r={st.pearson_r:.4f}")
            lines.append(f"f32: spikes={int(a.sum())} vs csr {int(b.sum())}, "
                         f"{st.summary()}, exact={exact}")
    report(f"blocked_fused n={c.n} {TILE_STEPS} steps", t0, lines)


def phase_serving(c, fw) -> None:
    import numpy as np

    from repro.core import simulate
    from repro.serving import COMPLETED, SimRequest, SimServeConfig, SimServer
    t0 = time.monotonic()
    params = {"n_sugar": fw.n_sugar, "rate_hz": fw.sugar_rate_hz}
    srv = SimServer(c, fw.sim, SimServeConfig(max_batch=SERVE_REQUESTS,
                                              chunk_steps=SERVE_CHUNK))
    reqs = [SimRequest(scenario="sugar_feeding", t_steps=SERVE_STEPS,
                       seed=s, params=params) for s in range(SERVE_REQUESTS)]
    done = srv.run(reqs)
    serve_s = time.monotonic() - t0
    check(len(done) == len(reqs), "every request came back")
    check(all(r.status == COMPLETED for r in reqs),
          f"all completed: {[(r.status, r.reason) for r in reqs]}")
    stim = sugar_stimulus(c, fw, srv.cfg)
    for r in reqs:
        solo = simulate(c, srv.cfg, SERVE_STEPS, stimulus=stim, seed=r.seed)
        check(same_run(solo, r.result), f"request {r.rid} == solo simulate()")
    st = srv.stats()
    report(f"SimServer n={c.n}", t0, [
        f"{len(reqs)} requests x {SERVE_STEPS} steps completed in "
        f"{st['batches']} batch(es), {serve_s:.1f} s",
        "spikes=" + str([int(np.asarray(r.result.counts).sum())
                         for r in reqs]),
        "each bit-equal to its solo simulate()"])


def phase_four_chips(fw, steps: int) -> None:
    import jax
    import numpy as np

    from repro.core import even_partition
    from repro.core.dcsr import build_dcsr
    from repro.core.distributed import (DistConfig, _run_partitioned,
                                        make_core_mesh, simulate_distributed)
    t0 = time.monotonic()
    c = paper_connectome(fw)
    d = build_dcsr(c, even_partition(c, 4),
                   quantize_bits=fw.sim.quantize_bits)
    dcfg = DistConfig(sim=fw.sim, scheme="event")
    stim = sugar_stimulus(c, fw, fw.sim)
    mesh = make_core_mesh(4)
    t1 = time.monotonic()
    sm = simulate_distributed(d, dcfg, steps, seed=0, mesh=mesh,
                              stimulus=stim)
    sm_s = time.monotonic() - t1
    t2 = time.monotonic()
    em = simulate_distributed(d, dcfg, steps, seed=0, emulate=True,
                              stimulus=stim)
    em_s = time.monotonic() - t2
    check(sm.counts.sum() > 0, "the partitioned network spikes")
    check(same(sm.counts, em.counts), "shard_map counts == emulated counts")
    check(sm.dropped == em.dropped,
          f"dropped {sm.dropped} == emulated {em.dropped}")
    check(all(same(a, b) for a, b in zip(sm.state, em.state)),
          "shard_map LIF state == emulated")
    # where the partitions ran: the per-partition carry of the same run
    keys = jax.random.split(jax.random.PRNGKey(0), d.n_parts)
    out, *_ = _run_partitioned(d, dcfg, steps, keys, None, stim, None, mesh,
                               emulate=False, trials=False)
    shards = {s.index[0].start or 0: s.device
              for s in out.counts.addressable_shards}
    check(len(set(shards.values())) == 4,
          f"4 partitions on 4 distinct devices: {shards}")
    check(same(np.asarray(out.counts).sum(), sm.counts.sum()),
          "placement run == checked run")
    report(f"shard_map P=4 vs emulated P=4, n={c.n}", t0, [
        f"U={d.part_size} steps={steps} spikes={int(sm.counts.sum())} "
        f"dropped={sm.dropped}",
        f"shard_map {sm_s:.1f} s, emulated {em_s:.1f} s",
        "counts, state and dropped bit-identical",
        "partition -> device id: " + ", ".join(
            f"{k}->{v.id}" for k, v in sorted(shards.items()))])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the partitioned simulation on 4 chips")
    args = ap.parse_args()

    # the CPU comparison needs the CPU backend next to the TPU one
    plat = os.environ.get("JAX_PLATFORMS", "")
    if plat and "cpu" not in plat.split(","):
        os.environ["JAX_PLATFORMS"] = plat + ",cpu"
    devs = tpu_devices(4 if args.four_chips else 1)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        sys.exit("chip_smoke: run from a checkout of the repository "
                 "(src/repro not found next to this script)")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs.flywire import CONFIG, SMOKE
    from repro.core import synthetic_flywire
    from repro.launch.compile_cache import enable_compile_cache
    print(f"[chip_smoke] compile cache: {enable_compile_cache()}", flush=True)

    if args.four_chips:
        phase_four_chips(CONFIG, PAPER_STEPS)
    else:
        phase_paper_scale(CONFIG, PAPER_STEPS, PAPER_TRIALS)
        phase_tpu_vs_cpu(SMOKE, PARITY_TRIALS)
        bench = synthetic_flywire(n=BENCH_N, target_synapses=BENCH_SYN,
                                  seed=0)
        phase_tile_path(bench, CONFIG)
        phase_serving(bench, CONFIG)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
