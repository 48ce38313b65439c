"""Readings of a cell's compared numbers, from which its limits are set.

    python3 bench/readings.py --workload <cell> --seeds 12 --control-seeds 3

For each seed, in one process on the cell's chips: the network and the
stimulus of call 0 as a run with that seed makes them, one call of the
simulator at the cell's own size, and the plain reference; the numbers
compared (``bench/compare.py``) are read for the simulator against the
reference and, on the first ``--control-seeds`` seeds, for the control (the
reference one precision step lower, in the simulator's place).  Prints one
JSON line per reading and a last line with the largest reading of the
simulator and the smallest of the control, per number.  The benchmark's
own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from bench import harness, program  # noqa: E402
from bench.compare import compared  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    args = ap.parse_args(argv)
    program.import_program()
    cell = harness.load_cell(args.workload)
    harness.enable_compile_cache()
    harness.require_devices(cell.chips)
    cfg, tr = cell.config, cell.traffic
    layout = cfg.get("partition")
    worst, least = {}, {}
    for k in range(args.seeds):
        seed = args.first_seed + k
        t0 = time.monotonic()
        net, conn = harness.network(cell)
        store = cell.entry.build(conn, cfg, tr)
        stim_seed, lanes = harness.call_seeds(seed, 0, tr)
        got = cell.entry.fetch(cell.entry.call(store, stim_seed, lanes))
        del store
        want = cell.reference.run_call(net, cfg["model"], tr, lanes,
                                       stim_seed, layout=layout)
        rows = [("program", compared(got, want, cfg["model"]))]
        if k < args.control_seeds:
            ctl = cell.reference.run_control_call(net, cfg["model"], tr,
                                                  lanes, stim_seed, layout)
            rows.append(("control", compared(ctl, want, cfg["model"])))
        for who, nums in rows:
            print(json.dumps({"seed": seed, "who": who, **nums,
                              "spikes": int(want.counts.sum()),
                              "s": round(time.monotonic() - t0, 1)}),
                  flush=True)
            for name, v in nums.items():
                if who == "program":
                    worst[name] = max(worst.get(name, v), v)
                else:
                    least[name] = min(least.get(name, v), v)
    print(json.dumps({"workload": args.workload, "program_max": worst,
                      "control_min": least}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
