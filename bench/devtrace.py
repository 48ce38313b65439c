"""Reduction of a ``jax.profiler`` trace to device busy, idle and
collective time over the benchmark's traced window.

``load`` reads the ``.xplane.pb`` a traced run wrote into plain lists: the
operations on each TPU's ``XLA Ops`` line, each as its HLO instruction
name with its result shape, its opcode, start and duration, and the
harness's own host spans (``window``, ``call``, ``fetch``).  ``reduce``
works on those lists only, so it is tested on a recorded trace without a
chip.

Busy time is the union of the intervals of leaf operations: a ``while``
(the scan over steps), ``conditional`` or ``call`` only contains other
operations, and the gaps between its body's operations are idle time.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

HOST_SPANS = ("window", "call", "fetch")
OPS_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call")
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all")
_HLO = re.compile(r"%?(?P<name>[^\s=]+) = (?P<type>\S+).*? (?P<op>[a-z][\w-]*)\(")


def op_label(hlo: str) -> tuple[str, str]:
    """``("fusion.115 s32[65536]", "fusion")`` from an op's HLO text."""
    m = _HLO.match(hlo)
    if m is None:
        return hlo[:80], hlo.split("(")[0][:40]
    shape = ("tuple" if m["type"].startswith("(")
             else m["type"].split("{")[0])
    return f"{m['name']} {shape}", m["op"]


def load(trace_dir: str) -> dict:
    """``{"devices": {plane: [[op, opcode, start_ns, dur_ns], ...]},
    "host": [[span, start_ns, dur_ns], ...]}`` from the trace in
    ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {paths}")
    pd = ProfileData.from_file(paths[0])
    devices, host = {}, []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [[*op_label(e.name), int(e.start_ns),
                             int(e.duration_ns)] for e in line.events]
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.name, int(e.start_ns), int(e.duration_ns)]
                         for e in line.events if e.name in HOST_SPANS]
    return {"devices": devices, "host": host}


def _union(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass(frozen=True)
class Reduction:
    window_s: float
    busy_s: float                  # union of op intervals, mean over chips
    collective_s: float | None     # None when no collective op ran
    op_s: dict                     # op name -> seconds, mean over chips
    gaps: list                     # [(seconds, host span), ...] longest first

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[name, s] for s, name in self.gaps[:top]]}


def _span_at(host: list, t: float) -> str:
    inner = [(d, name) for name, s, d in host
             if name != "window" and s <= t <= s + d]
    return min(inner)[1] if inner else "host"


def reduce(data: dict, chips: int) -> Reduction:
    """Busy, idle and collective time of the first ``chips`` TPUs over the
    host span ``window``; an op may carry fields after its fourth
    (``bench.layers.load``'s ``op_name``), which are not read here."""
    wins = [(s, s + d) for name, s, d in data["host"] if name == "window"]
    if len(wins) != 1:
        raise ValueError(f"expected one 'window' span, found {len(wins)}")
    ws, we = wins[0]
    planes = sorted(data["devices"],
                    key=lambda p: int(p.rsplit(":", 1)[1]))[:chips]
    if len(planes) < chips:
        raise ValueError(f"trace holds {len(planes)} TPUs, cell uses {chips}")
    busy, coll, op_s, gaps = [], [], {}, []
    any_coll = False
    for p in planes:
        ops = [(name, max(s, ws), min(s + d, we),
                bool(COLLECTIVE.search(f"{name} {op}")))
               for name, op, s, d, *_ in data["devices"][p]
               if s < we and s + d > ws and op not in CONTAINERS]
        segs = _union([[s, e] for _, s, e, _ in ops])
        busy.append(sum(e - s for s, e in segs))
        coll.append(sum(e - s for _, s, e, c in ops if c))
        any_coll |= any(c for *_, c in ops)
        for name, s, e, _ in ops:
            op_s[name] = op_s.get(name, 0.0) + (e - s) / 1e9 / chips
        edges = [ws] + [x for seg in segs for x in seg] + [we]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append(((b - a) / 1e9, _span_at(data["host"],
                                                     (a + b) / 2)))
    gaps.sort(key=lambda g: -g[0])
    return Reduction(window_s=(we - ws) / 1e9,
                     busy_s=sum(busy) / chips / 1e9,
                     collective_s=(sum(coll) / chips / 1e9 if any_coll
                                   else None),
                     op_s=op_s, gaps=gaps)


__all__ = ["Reduction", "load", "reduce"]
