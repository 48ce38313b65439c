"""Shared by the harness tests: a cell cut to a size a CPU test holds, and
stand-ins for the entry point."""

import json
import os
import time

from bench import harness, program

program.import_program()   # the simulator under test, from ``src``

TINY_NETWORK = {"n_neurons": 1003, "n_synapses": 60_000}
TINY_STEPS = 120
SEED = 2**31 + 5


# the partitioned cell, kept out of BENCHMARK.json until it is proved on
# four chips
P4 = {"name": "q19_p4_bg40", "config": "flywire_q19_p4",
      "traffic": "bg40_p4", "chips": 4}


def tiny_cell(name: str) -> harness.Cell:
    if name == P4["name"]:
        with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
            cell = harness.cell_from(P4, json.load(f))
    else:
        cell = harness.load_cell(name)
    cell.config["network"].update(TINY_NETWORK)
    cell.traffic["steps"] = TINY_STEPS
    return cell


def run(cell: harness.Cell, seed: int = SEED) -> dict:
    """One run with the harness's look for a chip skipped."""
    return harness.run_cell(cell, seed, 0.01, False, time.monotonic(),
                            require_tpu=False)


class ControlEntry:
    """The reference one precision step lower, in the program's place."""

    def __init__(self, cell: harness.Cell):
        self.cell = cell

    def build(self, conn, config, traffic):
        return conn

    def call(self, conn, stim_seed, lane_seeds):
        c = self.cell
        return c.reference.run_control_call(
            conn, c.config["model"], c.traffic, lane_seeds, stim_seed,
            layout=c.config.get("partition"))

    def fetch(self, answer):
        return answer
