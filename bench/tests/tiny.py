"""Shared by the harness tests: a cell cut to a size a CPU test holds, and
stand-ins for the entry point."""

import time

from bench import harness, program

program.import_program()   # the simulator under test, from ``src``

TINY_NETWORK = {"n_neurons": 1003, "n_synapses": 60_000}
TINY_STEPS = 120
SEED = 2**31 + 5
# The draws these tests were written against: the network from this seed,
# the stimulated population and the lanes' streams from each call's seed.
# At this size the control's drift flips a spike on most draws, not on
# every one.
TINY_DRAWS = {"seed": 181_218_205}


def tiny_cell(name: str) -> harness.Cell:
    cell = harness.load_cell(name)
    cell.config["network"].update(TINY_NETWORK, **TINY_DRAWS)
    cell.traffic["params"].pop("seed", None)
    cell.traffic.pop("streams_seed", None)
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
