"""The comparison that decides ``correct``: one call's answer against the
plain reference's answer for the same network, stimulus and seeds.

Two numbers are compared, each against the limit the configuration states:

* ``count_mismatch``: answer items that are not the reference's exactly,
  one per lane and neuron whose spike count or refractory count differs and
  one per lane whose drop total differs;
* ``state_gap_mV``: the widest gap between the program's final membrane
  potential or synaptic current and the reference's, over every lane and
  neuron, in mV (Q19.12 states are converted with ``w_scale / 2**12``).
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Answer:
    """One call's result: per lane, per neuron, in original neuron ids."""

    counts: np.ndarray     # [L, n] spike counts
    v: np.ndarray          # [L, n] membrane (Q19.12 integer, or mV float)
    g: np.ndarray          # [L, n] synaptic current, same units as v
    refrac: np.ndarray     # [L, n] refractory steps left
    dropped: np.ndarray    # [L] synapse events not delivered
    # the program's integer counters (``stats`` of its result), each
    # summed over chips, per lane or a total; never compared
    stats: dict = dataclasses.field(default_factory=dict)


def _mv(x: np.ndarray, model: dict) -> np.ndarray:
    if model["fixed_point"]:
        return x.astype(np.float64) * (model["lif"]["w_scale"] / 4096.0)
    return x.astype(np.float64)


def compared(got: Answer, want: Answer, model: dict) -> dict:
    """``{number name: value}`` for one call."""
    if got.counts.shape != want.counts.shape:
        return {"count_mismatch": int(want.counts.size + want.dropped.size),
                "state_gap_mV": float("inf")}
    items = (got.counts != want.counts) | (got.refrac != want.refrac)
    count = int(items.sum() + (got.dropped != want.dropped).sum())
    gap = max(np.abs(_mv(got.v, model) - _mv(want.v, model)).max(),
              np.abs(_mv(got.g, model) - _mv(want.g, model)).max())
    return {"count_mismatch": count, "state_gap_mV": float(gap)}


__all__ = ["Answer", "compared"]
