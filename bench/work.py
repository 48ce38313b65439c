"""The yardstick of ``step_mfu``: the least time the chip could take for the
model's required work, whatever engine or kernel does the step.

Required work per simulated step of one lane, counted from the model and
not from the implementation (no budget slots, no HLO bytes):

* every neuron's LIF state and spike count, four 4-byte words (v, g,
  refractory steps, count), read once and written once: 32 bytes;
* one row of the delay ring read and one written, one byte per neuron;
* every delivered synapse event reads its target index and weight (4 + 4
  bytes) and reads and writes its target's accumulator (4 + 4 bytes): 16
  bytes and one add;
* the LIF update of every neuron: ``LIF_OPS`` operations (forward Euler of
  Eq. 1: input add, leak and add on v, decay of g, threshold, two resets,
  refractory count; the fixed-point path adds four shifts).

The least time is the larger of operations over the peak operation rate and
bytes over the peak HBM bandwidth (``bench/peaks.json``, by device kind).
"""

from __future__ import annotations

import dataclasses
import json
import os

NEURON_BYTES = 2 * 4 * 4 + 2
EVENT_BYTES = 16
LIF_OPS = {False: 10, True: 14}     # by fixed_point


def peaks(kind: str) -> dict:
    """The published peaks of ``kind``; an unknown kind is an error."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)["kinds"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[kind]


def required(n: int, lane_steps: int, events: int,
             fixed_point: bool) -> tuple[int, int]:
    """``(operations, bytes)`` the model requires for ``lane_steps`` steps
    (summed over lanes) of ``n`` neurons delivering ``events`` events."""
    ops = lane_steps * n * LIF_OPS[bool(fixed_point)] + events
    nbytes = lane_steps * n * NEURON_BYTES + events * EVENT_BYTES
    return ops, nbytes


def least_seconds(ops: int, nbytes: int, peak: dict) -> float:
    return max(ops / peak["ops_per_s"], nbytes / peak["hbm_bytes_per_s"])


@dataclasses.dataclass(frozen=True)
class Measure:
    """What the per-layer readers see: one traced window."""

    window_s: float
    busy_s: float                  # mean over the cell's chips
    collective_s: float | None     # mean over the cell's chips
    steps: int                     # simulated steps (one per lane batch)
    chips: int
    least_s: float                 # required work at the chips' peak

    @classmethod
    def of(cls, red, win, config: dict, events: int, device) -> "Measure":
        steps = win.calls * win.steps
        ops, nbytes = required(int(config["network"]["n_neurons"]),
                               steps * win.lanes, events,
                               config["model"]["fixed_point"])
        return cls(window_s=red.window_s, busy_s=red.busy_s,
                   collective_s=red.collective_s, steps=steps,
                   chips=win.chips,
                   least_s=least_seconds(ops, nbytes,
                                         peaks(device.device_kind)))


__all__ = ["EVENT_BYTES", "LIF_OPS", "Measure", "NEURON_BYTES",
           "least_seconds", "peaks", "required"]
