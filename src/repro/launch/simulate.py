"""FlyWire simulation driver (the paper's workload as a CLI).

    PYTHONPATH=src python -m repro.launch.simulate --scale smoke \
        --scenario sugar_feeding --engine event --trials 3
    PYTHONPATH=src python -m repro.launch.simulate --scale full --parity
    PYTHONPATH=src python -m repro.launch.simulate --distributed --cores 4

--scenario selects a registered stimulus scenario (repro.exp.scenarios);
--trials > 1 runs a vmapped seed batch — one compiled call — and reports
trial-averaged rates (on the distributed path too: the unified step core
batches the partitioned scan the same way).  --distributed partitions
with the paper's greedy capacity scheme and runs the shard_map simulator
with the same stimulus pytree (one partition per host device; set
XLA_FLAGS=--xla_force_host_platform_device_count=N first, or use
--emulate); --dist-scheme selects the registered exchange scheme
(bitmap | event | blocked).
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import time

import numpy as np

from repro import obs
from repro.configs.flywire import CONFIG, CONFIG_1MS, SMOKE
from repro.core import (CoreBudget, SimConfig, caps_from_budget,
                        greedy_partition, parity, spike_rates_hz,
                        synthetic_flywire_cached)
from repro.core.dcsr import build_dcsr
from repro.core.distributed import DistConfig, simulate_distributed
from repro.exp import (available_scenarios, build_scenario, get_scenario,
                       run_dist_trials, run_trials)
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", choices=["smoke", "bench", "full"],
                    default="bench")
    from repro.core import available_engines
    ap.add_argument("--engine", default="event",
                    choices=available_engines())
    ap.add_argument("--scenario", default="sugar_feeding",
                    choices=available_scenarios())
    ap.add_argument("--dt", type=float, default=0.1, choices=[0.1, 1.0])
    ap.add_argument("--fixed-point", action="store_true",
                    help="run the int32 Q19.12 integration path (the "
                         "Loihi-faithful arithmetic; CI smokes it on "
                         "every push)")
    ap.add_argument("--trials", type=int, default=1)
    ap.add_argument("--t-ms", type=float, default=0.0)
    ap.add_argument("--background-hz", type=float, default=None,
                    help="override the scenario's background_hz param "
                         "(0 turns an always-on background off)")
    ap.add_argument("--parity", action="store_true",
                    help="compare against the float csr reference")
    ap.add_argument("--distributed", action="store_true")
    ap.add_argument("--emulate", action="store_true")
    ap.add_argument("--cores", type=int, default=4)
    from repro.core import available_schemes
    ap.add_argument("--dist-scheme", default="event",
                    choices=sorted(set(available_schemes()) - {"local"}))
    # Chunked supervision / checkpoint-resume (docs/resilience.md): the
    # CI kill-and-resume smoke drives these end to end.
    ap.add_argument("--chunk-steps", type=int, default=0,
                    help="supervise the run in K-step chunks "
                         "(bit-identical to the monolithic scan; 0 = off)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="checkpoint the carry at chunk boundaries")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest checkpoint in "
                         "--checkpoint-dir")
    ap.add_argument("--health", action="store_true",
                    help="enable in-scan health sentinels + chunk-boundary "
                         "threshold checks")
    ap.add_argument("--max-drop-rate", type=float, default=None,
                    help="health threshold: dropped synapse events per "
                         "step (implies --health)")
    ap.add_argument("--inject-fail-at-chunk", type=int, default=0,
                    help="deterministic mid-run kill: run only N chunks "
                         "then exit (requires --chunk-steps and "
                         "--checkpoint-dir; resume with --resume)")
    ap.add_argument("--digest", action="store_true",
                    help="print a sha256 over raster+counts (enables the "
                         "raster probe; the kill-and-resume smoke's "
                         "bit-identity check)")
    # Telemetry + profiling (docs/observability.md): the CI telemetry
    # smoke drives --telemetry end to end (emit -> schema check -> report).
    ap.add_argument("--telemetry", default=None, metavar="PATH",
                    help="stream JSONL telemetry events to PATH "
                         "(chunk/compile/span/health records; inspect with "
                         "python -m repro.obs.report PATH)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="wrap the run in jax.profiler.trace(DIR) "
                         "(TensorBoard-loadable XLA trace)")
    args = ap.parse_args()

    supervised = bool(args.chunk_steps or args.checkpoint_dir or args.resume
                      or args.health or args.max_drop_rate is not None)
    if supervised and args.trials > 1:
        ap.error("chunked supervision flags require --trials 1")
    if args.inject_fail_at_chunk and not (args.chunk_steps
                                          and args.checkpoint_dir):
        ap.error("--inject-fail-at-chunk requires --chunk-steps and "
                 "--checkpoint-dir")

    with contextlib.ExitStack() as stack:
        if args.telemetry:
            stack.enter_context(obs.telemetry(args.telemetry))
        stack.enter_context(obs.profile_trace(args.profile))
        _run(args, supervised)
    if args.telemetry:
        print(f"[simulate] telemetry stream: {args.telemetry} "
              f"(python -m repro.obs.report {args.telemetry})")


def _fmt_stats(stats: dict) -> str:
    """Render result stats for the run line; nested dicts (the telemetry
    compile-cache snapshot) get a compact hit/miss summary."""
    out = []
    for k, v in stats.items():
        if isinstance(v, dict):
            if k == "compile_cache":
                out.append(f" cache_hits={v['hits']}"
                           f"/{v['hits'] + v['misses']}")
            continue
        out.append(f" {k}={int(np.asarray(v).sum())}")
    return "".join(out)


def _run(args, supervised: bool):
    fw = {"smoke": SMOKE, "bench": dataclasses.replace(
        SMOKE, n_neurons=20_000, target_synapses=600_000, t_sim_ms=100.0),
        "full": (CONFIG if args.dt == 0.1 else CONFIG_1MS)}[args.scale]
    c = synthetic_flywire_cached(n=fw.n_neurons, seed=0,
                                 target_synapses=fw.target_synapses)
    print(f"[simulate] connectome: {c.stats()}")
    t_ms = args.t_ms or fw.t_sim_ms
    cfg = dataclasses.replace(fw.sim, engine=args.engine,
                              fixed_point=fw.sim.fixed_point
                              or args.fixed_point)
    if args.health or args.max_drop_rate is not None:
        from repro.core import HealthConfig
        cfg = dataclasses.replace(
            cfg, health=HealthConfig(max_drop_rate=args.max_drop_rate))
    t_steps = int(round(t_ms / cfg.params.dt))
    dt_ms = cfg.params.dt
    if args.inject_fail_at_chunk:
        # deterministic "kill": stop after N supervised chunks; the
        # checkpoints on disk are exactly what a SIGKILL would leave
        t_steps = min(t_steps, args.inject_fail_at_chunk * args.chunk_steps)
    probes = None
    if args.digest:
        from repro.exp.probes import ProbeSpec
        probes = ProbeSpec(raster=True)
    chunk_kw = dict(chunk_steps=args.chunk_steps or None,
                    checkpoint_dir=args.checkpoint_dir, resume=args.resume)

    scen = get_scenario(args.scenario)
    # FlyWireConfig stays the source of truth for the sugar population
    # wherever the scenario exposes the matching params
    overrides = {}
    if "n_sugar" in scen.defaults:
        overrides["n_sugar"] = fw.n_sugar
    if "rate_hz" in scen.defaults:
        overrides["rate_hz"] = fw.sugar_rate_hz
    if args.background_hz is not None:
        if "background_hz" in scen.defaults:
            overrides["background_hz"] = args.background_hz
        else:
            print(f"[simulate] note: scenario {scen.name!r} takes no "
                  f"background_hz; --background-hz ignored")
    stim = build_scenario(args.scenario, c, cfg, **overrides)
    print(f"[simulate] scenario {scen.name!r}: {scen.description}")

    if args.distributed:
        caps = caps_from_budget(CoreBudget.tpu_vmem(), "sar")
        p = greedy_partition(c, caps, scheme="sar")
        from repro.core.partition import pad_to_uniform
        p = pad_to_uniform(p, args.cores, c.n)
        d = build_dcsr(c, p, quantize_bits=cfg.quantize_bits)
        print(f"[simulate] distributed over {d.n_parts} partitions "
              f"(U={d.part_size}, S_max={d.s_max}, "
              f"scheme={args.dist_scheme})")
        dcfg = DistConfig(sim=cfg, scheme=args.dist_scheme)
        t0 = time.time()
        raster = None
        if args.trials > 1:
            res = run_dist_trials(d, dcfg, t_steps, seeds=args.trials,
                                  emulate=args.emulate, stimulus=stim)
            mean_counts = np.asarray(res.counts, np.float64).mean(axis=0)
            dropped = int(np.asarray(res.dropped).sum())
        else:
            res = simulate_distributed(d, dcfg, t_steps, seed=0,
                                       emulate=args.emulate, stimulus=stim,
                                       probes=probes, **chunk_kw)
            mean_counts = res.counts.astype(np.float64)
            dropped = res.dropped
            raster = res.raster
        stats = _fmt_stats(res.stats)
        print(f"[simulate] {max(args.trials, 1)} trial(s) x {t_steps} steps "
              f"in {time.time()-t0:.2f}s (dropped={dropped}{stats})")
    elif supervised or (args.telemetry and args.trials == 1):
        # a single-trial telemetry run goes through simulate() so the
        # full run_start/chunk/run_end event stream exists
        from repro.core import simulate
        t0 = time.time()
        res = simulate(c, cfg, t_steps, stimulus=stim, probes=probes,
                       seed=0, **chunk_kw)
        mean_counts = np.asarray(res.counts, np.float64)
        dropped = int(np.asarray(res.dropped))
        raster = res.raster
        stats = _fmt_stats(res.stats)
        print(f"[simulate] 1 trial x {t_steps} supervised steps "
              f"(K={args.chunk_steps or t_steps}) in {time.time()-t0:.2f}s "
              f"(dropped={dropped}{stats})")
    else:
        t0 = time.time()
        raster = None
        res = run_trials(c, cfg, t_steps, stimulus=stim, seeds=args.trials,
                         probes=probes)
        mean_counts = np.asarray(res.counts, np.float64).mean(axis=0)
        dropped = int(np.asarray(res.dropped).sum())
        print(f"[simulate] {args.trials} trial(s) x {t_steps} steps in "
              f"{time.time()-t0:.2f}s (dropped={dropped})")
    if args.inject_fail_at_chunk:
        print(f"[simulate] injected kill after chunk "
              f"{args.inject_fail_at_chunk} — checkpoints in "
              f"{args.checkpoint_dir}; rerun with --resume to continue")
        return
    if args.digest:
        import hashlib
        h = hashlib.sha256()
        if raster is not None:
            h.update(np.ascontiguousarray(np.asarray(raster)).tobytes())
        h.update(np.ascontiguousarray(
            mean_counts.astype(np.int64)).tobytes())
        print(f"[simulate] digest {h.hexdigest()}")

    rates = np.asarray(spike_rates_hz(mean_counts, t_steps, dt_ms))
    active = (rates > 0.5).sum()
    print(f"[simulate] mean total spikes {mean_counts.sum():.1f}, "
          f"active neurons {active} ({active/c.n:.2%}), "
          f"mean active rate {rates[rates>0.5].mean() if active else 0:.1f} Hz")

    if args.parity:
        ref_cfg = SimConfig(engine="csr", params=cfg.params,
                            poisson_to_v=True)
        ref_stim = build_scenario(args.scenario, c, ref_cfg, **overrides)
        ra = run_trials(c, ref_cfg, t_steps, stimulus=ref_stim,
                        seeds=[10 + i for i in range(args.trials)]
                        ).mean_rates_hz(t_steps, dt_ms)
        rb = run_trials(c, cfg, t_steps, stimulus=stim,
                        seeds=[20 + i for i in range(args.trials)]
                        ).mean_rates_hz(t_steps, dt_ms)
        print("[simulate] parity vs float reference:",
              parity(ra, rb).summary())


if __name__ == "__main__":
    main()
