"""Benchmark command: ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``, run from the root of a checkout on a machine
that holds the chips the cell asks for.  See ``bench/harness.py``."""

import os
import sys
import time

T_START = time.monotonic()

if __name__ == "__main__":
    # the TPU runtime otherwise logs to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    from bench.harness import main
    sys.exit(main(T_START))
