"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--smoke] [--only NAME] \
        [--json BENCH_engine_step.json]

Prints ``name,value,derived`` CSV rows; ``--json PATH`` additionally
writes every row (plus backend/host metadata) machine-readably so each
perf PR leaves a comparable trajectory point.  --full runs at the paper's
139,255-neuron scale (slower; cached after first run); --smoke runs
supporting modules at CI-tiny scale (a harness-breakage canary, not a
measurement).  A module that raises is recorded as an explicit
``<module>.error`` row (and fails the exit code) instead of aborting the
remaining modules.
"""

from __future__ import annotations

import argparse
import inspect
import sys
import time
import traceback

MODULES = [
    "bench_connectome_stats",   # Figs 2-3
    "bench_compression",        # Fig 7
    "bench_partition",          # Figs 8-10, chip counts
    "bench_parity",             # Figs 6/12/13/14/15
    "bench_activity_scaling",   # Table 1, Figs 16-17, engine_step.* rows
    "bench_serving",            # serving-layer throughput + latency
]


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper scale (139k neurons)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-tiny scale for modules that support it "
                         "(harness canary, not a measurement)")
    ap.add_argument("--only", default="")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="write all rows + metadata as JSON to PATH")
    args = ap.parse_args()

    import importlib

    from .common import row, write_json

    print("name,value,derived")
    t0 = time.time()
    results: dict[str, list] = {}
    failed = []
    for name in MODULES:
        if args.only and args.only not in name:
            continue
        t = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            kw = {"full": args.full}
            if "smoke" in inspect.signature(mod.run).parameters:
                kw["smoke"] = args.smoke
            results[name] = mod.run(**kw) or []
        except Exception as e:  # noqa: BLE001 — surfaced as an .error row
            traceback.print_exc(file=sys.stderr)
            results[name] = [row(f"{name}.error", type(e).__name__, str(e))]
            failed.append(name)
        print(f"# {name} done in {time.time()-t:.1f}s", file=sys.stderr)
    print(f"# total {time.time()-t0:.1f}s", file=sys.stderr)
    if args.json:
        write_json(args.json, results, full=args.full, smoke=args.smoke)
        print(f"# wrote {args.json}", file=sys.stderr)
    if failed:
        sys.exit(f"benchmark modules failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
