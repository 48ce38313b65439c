"""The yardstick of ``step_mfu``: the least time the chip could take for the
model's required work, whatever engine or kernel does the step.

Each configuration states its model's required work as data, in its
``work`` block: bytes and operations per neuron and lane-step, and per
delivered synapse event.  They are counted from the model and not from
the implementation (no budget slots, no HLO bytes).  For the two-state
current-based LIF with one uniform delay (``flywire_*``):

* ``neuron_bytes`` 34: every neuron's LIF state and spike count, four
  4-byte words (v, g, refractory steps, count), read once and written
  once: 32 bytes; one row of the delay ring read and one written, one byte
  per neuron;
* ``neuron_ops`` 10, or 14 in Q19.12: the LIF update (forward Euler of
  Eq. 1: input add, leak and add on v, decay of g, threshold, two resets,
  refractory count; the fixed-point path adds four shifts);
* ``event_bytes`` 16 and ``event_ops`` 1: every delivered synapse event
  reads its target index and weight (4 + 4 bytes) and reads and writes its
  target's accumulator (4 + 4 bytes), and adds once.

The least time is the larger of operations over the peak operation rate and
bytes over the peak HBM bandwidth (``bench/peaks.json``, by device kind).
"""

from __future__ import annotations

import dataclasses
import json
import os

def peaks(kind: str) -> dict:
    """The published peaks of ``kind``; an unknown kind is an error."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)["kinds"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[kind]


def required(work: dict, n: int, lane_steps: int,
             events: int) -> tuple[int, int]:
    """``(operations, bytes)`` that a configuration's ``work`` block
    requires for ``lane_steps`` steps (summed over lanes) of ``n`` neurons
    delivering ``events`` events."""
    ops = lane_steps * n * int(work["neuron_ops"]) + events * int(
        work["event_ops"])
    nbytes = lane_steps * n * int(work["neuron_bytes"]) + events * int(
        work["event_bytes"])
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
    # device ms per simulated step by the step's layers and by part of
    # delivery, mean over the chips (``bench.layers.reduce``); empty for a
    # program that names no layers
    layers: dict
    # the program's counters per simulated step, summed over lanes and
    # chips (``stats`` of each call's result)
    counters: dict

    @classmethod
    def of(cls, red, win, work: dict, n: int, events: int, device,
           layers: dict, counters: dict) -> "Measure":
        """One traced window: ``red`` its ``devtrace.reduce``, ``win`` the
        harness's ``Window``, ``work`` the configuration's ``work`` block,
        ``n`` the network's neurons."""
        steps = win.calls * win.steps
        ops, nbytes = required(work, n, steps * win.lanes, events)
        return cls(window_s=red.window_s, busy_s=red.busy_s,
                   collective_s=red.collective_s, steps=steps,
                   chips=win.chips,
                   least_s=least_seconds(ops, nbytes,
                                         peaks(device.device_kind)),
                   layers=dict(layers), counters=dict(counters))


__all__ = ["Measure", "least_seconds", "peaks", "required"]
