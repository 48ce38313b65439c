"""Per-layer split of a traced window: device time by the step's named
layers, and idle time by the host span that was open.

The program names the layers of its step on the device with
``jax.named_scope`` (the names are declared once, in ``repro.obs.scopes``)
and its host work on the hot path with ``repro.obs.span``, which writes a
``repro.<name>`` annotation into the profiler trace.  ``load`` reads a
trace as ``devtrace.load`` does, keeping each device operation's
``op_name`` as a fifth field and every ``repro.*`` host span; ``reduce``
splits the window's busy time by layer:

* an operation belongs to the innermost layer scope on its ``op_name``
  path (the last component, the primitive, is never read); operations
  under no scope are ``other`` (the scan's loop control, copies XLA
  inserted, per-call set-up outside the scan);
* a layer's time is the union of its leaf operations' intervals in the
  window, a mean over the cell's chips; the parts of ``delivery``
  (``repro.obs.scopes.DELIVERY_PARTS``) roll up into it and are also
  listed on their own;
* every idle gap goes to the innermost host span around its middle
  (``devtrace.reduce``), so the program's ``repro.*`` spans name what the
  host was doing; ``call`` is what no span of the program covers.

A program that declares scopes but whose trace holds no scoped operation
is an error, never a row of zeros: a persistent compile cache keyed
without metadata can hand back an executable compiled from a tree
without scopes.  ``harness.enable_compile_cache`` keys the cache with the
programs' metadata for that reason.  A program that declares no scopes
(an older tree) reduces to nothing.

    python3 -m bench.layers --workload <cell> --seed <n> --seconds <s> \\
        [--out FILE] [--fixture FILE]

runs the cell as ``bench/run.py`` does (set-up and warm-up), then one
untraced and one traced window of ``--seconds`` each, and prints one JSON
line: the cell's end-to-end rates in both windows (the cost of tracing
on), device ms per simulated step by layer and by part of delivery, the
share of busy time the named layers cover, and idle ms per call by host
span.  ``--fixture`` writes a short cut of the trace (a call boundary and
the steps after it) for ``bench/tests``.  It runs where ``bench/run.py``
runs: from the root of a checkout, on a machine that holds the cell's
chips.
"""

from __future__ import annotations

import argparse
import bisect
import collections
import contextlib
import dataclasses
import glob
import json
import os
import re
import shutil
import sys
import tempfile
import time

from bench import devtrace

OTHER = "other"
PROGRAM_SPAN = "repro."
MODULES_LINE = "XLA Modules"
_WRAPPED = re.compile(r"[\w.-]*\((.*)\)")


def scope_names():
    """``(layers, delivery_parts)`` as the program declares them, or None
    for a program that declares none."""
    try:
        from repro.obs import scopes
    except ImportError:
        return None
    return tuple(scopes.LAYERS), tuple(scopes.DELIVERY_PARTS)


def layer_of(op_name: str, names) -> str:
    """The innermost of ``names`` on the ``op_name`` path, else ``other``.
    Transform wrappers such as ``vmap(...)`` are looked through."""
    for part in reversed(op_name.split("/")[:-1]):
        while (m := _WRAPPED.fullmatch(part)):
            part = m[1]
        if part in names:
            return part
    return OTHER


_INSTR = re.compile(r"\s*(?:ROOT )?%([\w.-]+) = (.*)$")
_COMPUTATION = re.compile(r"(?:ENTRY )?%?([\w.-]+) ")
_REF = re.compile(r"%([\w.-]+)")
_CALLS = re.compile(r"(?:calls|to_apply)=%([\w.-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def hlo_op_names(hlo_text: str) -> tuple[str, dict]:
    """``(module name, {instruction: op_name})`` from a program's
    optimized HLO text.  An instruction that carries no layer scope of its
    own (XLA's passes make some without metadata: copies, the
    reduce-window trees of a cumulative sum, a fusion whose root has
    none) takes, in this order:

    * a collective, the exchange's: the exchange is the only layer that
      may communicate (``repro.core.exchange.base``), and the TPU compiler
      remakes its all-gather as an all-reduce that carries no layer scope;
    * an instruction that calls computations (a fusion, a scatter's or a
      reduction's combiner), the scope of the operations they hold, from
      their roots down through each one's own calls and then its operands:
      what it computes, not what uses it.  The TPU compiler leaves a
      scatter without metadata, but its combiner keeps the scope (the
      vmapped compaction's ``where`` scatter, fused and feeding the slot
      loop, is compaction's; the ``segment_sum`` scatter, fed by the
      synapse gather, is ``syn_scatter``'s);
    * otherwise the ``op_name`` of the nearest instruction of its
      computation that uses its result and has one."""
    module = hlo_text.split(None, 2)[1].rstrip(",")
    names = scope_names()
    every = names[0] + names[1] if names else ()
    op_name, users, operands, collective, calls, root = {}, {}, {}, {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            head = _COMPUTATION.match(line)
            comp = head[1] if head else None
            body = set()             # a new computation: its own names
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        name, rest = m.groups()
        found = _OP_NAME.search(rest)
        op_name[name] = found[1] if found else ""
        opcode = devtrace.op_label(f"%{name} = {rest}")[1]
        if devtrace.COLLECTIVE.match(opcode):
            collective[name] = opcode
        head = rest.split(", metadata=")[0]
        if (called := _CALLS.findall(head)):
            calls[name] = called
        if line.lstrip().startswith("ROOT "):
            root[comp] = name
        operands[name] = [r for r in set(_REF.findall(head))
                          if r in body and r != name]
        body.add(name)
        for ref in operands[name]:
            users.setdefault(ref, []).append(name)
    scoped = {n: o for n, o in op_name.items()
              if layer_of(o, every) != OTHER}
    if names:
        from repro.obs.scopes import EXCHANGE
        for name, opcode in collective.items():
            scoped.setdefault(name, f"{EXCHANGE}/{opcode}")

    def called_scope(name, seen):
        queue = collections.deque(root.get(c) for c in calls.get(name, ()))
        while queue:
            x = queue.popleft()
            if x is None or x in seen:
                continue
            seen.add(x)
            if (o := scoped.get(x) or called_scope(x, seen)):
                return o
            queue.extend(sorted(operands.get(x, ())))
        return ""

    for name in [n for n in calls if n not in scoped]:
        if (o := called_scope(name, {name})):
            scoped[name] = o

    def inherit(name):
        queue, seen = collections.deque(users.get(name, ())), {name}
        while queue:
            user = queue.popleft()
            if user in scoped:
                return scoped[user]
            if user not in seen:
                seen.add(user)
                queue.extend(users.get(user, ()))
        return ""

    for name, o in op_name.items():
        op_name[name] = scoped.get(name) or inherit(name) or o
    return module, op_name


@contextlib.contextmanager
def compiled_programs():
    """Collects the optimized HLO text of every program that the block
    runs through the simulator's jitted entry points
    (``repro.obs.InstrumentedJit``), by module name.  Each program is
    compiled again ahead of time for the same argument shapes, which the
    persistent compile cache answers with the executable that ran,
    metadata and all (the compiler is deterministic besides, so a fresh
    compile names its instructions alike).  A TPU
    trace names an operation only by its HLO instruction: the ``op_name``
    that carries its layer is in the program's HLO."""
    import jax
    from repro.obs.jit import InstrumentedJit
    texts: dict = {}
    real = InstrumentedJit.__call__

    def spec(x):
        if not isinstance(x, jax.Array):
            return x
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, weak_type=x.aval.weak_type,
            sharding=x.sharding if x.committed else None)

    def call(self, *args):
        specs = jax.tree.map(spec, args)
        out = real(self, *args)
        text = self.fn.lower(*specs).compile().as_text()
        texts[text.split(None, 2)[1].rstrip(",")] = text
        return out

    InstrumentedJit.__call__ = call
    try:
        yield texts
    finally:
        InstrumentedJit.__call__ = real


def load(trace_dir: str, programs: dict) -> dict:
    """``devtrace.load``'s lists, each device op as ``[op, opcode,
    start_ns, dur_ns, op_name]``, and the host spans ``window``, ``call``,
    ``fetch`` and every ``repro.*`` span.  An op's ``op_name`` comes from
    ``programs`` (HLO text by module name, ``compiled_programs``), found
    by the module the device was running at the op's start (the plane's
    ``XLA Modules`` line); ops of other programs get none."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {paths}")
    pd = ProfileData.from_file(paths[0])
    op_names = dict(hlo_op_names(t) for t in programs.values())
    devices, host = {}, []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            lines = {line.name: list(line.events) for line in plane.lines}
            runs = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                           e.name.split("(")[0])
                          for e in lines.get(MODULES_LINE, ()))
            starts = [r[0] for r in runs]
            ops, labels = [], {}
            for e in lines.get(devtrace.OPS_LINE, ()):
                name = e.name
                if name not in labels:
                    labels[name] = devtrace.op_label(name)
                label, opcode = labels[name]
                s = int(e.start_ns)
                k = bisect.bisect_right(starts, s) - 1
                module = runs[k][2] if k >= 0 and s < runs[k][1] else ""
                ops.append([label, opcode, s, int(e.duration_ns),
                            op_names.get(module, {}).get(
                                label.split(" ")[0], "")])
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            host += [[e.name, int(e.start_ns), int(e.duration_ns)]
                     for line in plane.lines for e in line.events
                     if e.name in devtrace.HOST_SPANS
                     or e.name.startswith(PROGRAM_SPAN)]
    return {"devices": devices, "host": host}


@dataclasses.dataclass(frozen=True)
class Layers:
    """A traced window by layer; times in ms per simulated step (mean over
    chips), idle in ms per call."""

    busy: float                # device ms per step, all operations
    named_share: float         # share of busy time under some layer scope
    layers: dict               # layer (and "other") -> ms per step
    parts: dict                # part of delivery -> ms per step
    idle: dict                 # host span -> idle ms per call

    def metrics(self, suffix: str = "") -> dict:
        """The per-layer metrics, by the names ``BENCHMARK.json`` would
        give them (``suffix`` ``.trials`` for a trial battery's cell); a
        layer with no operation in the window (the exchange on one chip)
        has none."""
        return {f"{layer}_ms_per_step{suffix}": self.layers[layer]
                for layer in ("delivery", "compaction", "stimulus", "lif",
                              "exchange") if self.layers.get(layer)}


def reduce(data: dict, chips: int, steps: int, calls: int,
           names=None, base=None) -> Layers | None:
    """Split the window of ``data`` (``load``'s lists) by layer; ``steps``
    and ``calls`` are the window's simulated steps and calls, ``base`` its
    ``devtrace.reduce`` where the caller has it.  None where the program
    declares no scopes."""
    names = names if names is not None else scope_names()
    if names is None:
        return None
    layers, parts = names
    base = base or devtrace.reduce(data, chips)
    (ws, we), = [(s, s + d) for n, s, d in data["host"] if n == "window"]
    planes = sorted(data["devices"],
                    key=lambda p: int(p.rsplit(":", 1)[1]))[:chips]
    total: dict = {}
    named = 0
    for p in planes:
        spans: dict = {}
        for _, opcode, s, d, op_name in data["devices"][p]:
            a, b = max(s, ws), min(s + d, we)
            if b <= a or opcode in devtrace.CONTAINERS:
                continue
            layer = layer_of(op_name, layers + parts)
            spans.setdefault(layer, []).append([a, b])
            if layer in parts:
                spans.setdefault("delivery", []).append([a, b])
        for layer, iv in spans.items():
            total[layer] = total.get(layer, 0) + sum(
                e - s for s, e in devtrace._union(iv))
        named += sum(e - s for s, e in devtrace._union(
            [iv for layer, ivs in spans.items() if layer != OTHER
             for iv in ivs]))
    if not named:
        raise ValueError(
            "the trace holds no operation under a layer scope of "
            "repro.obs.scopes: the program ran without its op_name "
            "metadata (was the executable taken from a compile cache "
            "keyed without it?)")
    per_step = 1e3 / 1e9 / chips / steps
    busy_ns = base.busy_s * 1e9 * chips
    idle: dict = {}
    for seconds, span in base.gaps:        # every chip's gaps
        idle[span] = idle.get(span, 0.0) + 1e3 * seconds / calls / chips
    return Layers(
        busy=1e3 * base.busy_s / steps, named_share=named / busy_ns,
        layers={k: total.get(k, 0) * per_step for k in layers + (OTHER,)},
        parts={k: total.get(k, 0) * per_step for k in parts},
        idle=dict(sorted(idle.items(), key=lambda kv: -kv[1])))


def cut(data: dict, start_ns: int, stop_ns: int) -> dict:
    """The part of ``data`` in ``[start_ns, stop_ns)``, that interval being
    its window: every op and host span that overlaps it, whole."""
    def keep(s, d):
        return s < stop_ns and s + d > start_ns
    return {"devices": {p: [op for op in ops if keep(op[2], op[3])]
                        for p, ops in data["devices"].items()},
            "host": [["window", start_ns, stop_ns - start_ns]]
            + [h for h in data["host"]
               if h[0] != "window" and keep(h[1], h[2])]}


# --------------------------------------------------------------------------
# A run of one cell, on the chip
# --------------------------------------------------------------------------

def _window(cell, store, seed: int, seconds: float, first: int,
            trace_dir: str | None):
    """Whole calls, numbered from ``first``, until ``seconds`` have
    passed, with the harness's annotations; ``(wall seconds, calls)``."""
    import jax
    from bench.harness import call_seeds
    n = 0
    with (jax.profiler.trace(trace_dir) if trace_dir
          else contextlib.nullcontext()):
        with jax.profiler.TraceAnnotation("window"):
            t0 = time.monotonic()
            while True:
                with jax.profiler.TraceAnnotation("call"):
                    r = cell.entry.call(store,
                                        *call_seeds(seed, first + n,
                                                    cell.traffic))
                with jax.profiler.TraceAnnotation("fetch"):
                    cell.entry.fetch(r)
                n += 1
                if time.monotonic() - t0 >= seconds:
                    break
            wall = time.monotonic() - t0
    return wall, n


def run(cell, seed: int, seconds: float, fixture: str | None = None,
        *, require_tpu: bool = True) -> dict:
    """Set-up and warm-up as ``bench/run.py``'s, an untraced and a traced
    window of ``seconds`` each, and their reduction; the result line's
    object.  ``fixture`` is a path for a cut of the trace."""
    import jax
    from bench import harness
    if require_tpu:
        harness.require_devices(cell.chips)
    cfg, tr = cell.config, cell.traffic
    steps, lanes = int(tr["steps"]), int(tr["lanes"])
    store = cell.entry.build(harness.network(cell)[1], cfg, tr)
    with compiled_programs() as programs:
        cell.entry.fetch(cell.entry.call(
            store, *harness.call_seeds(seed, -1, tr)))
    rates = {}
    tdir = tempfile.mkdtemp(prefix="bench_layers_")
    try:
        first = 0
        for name, trace_dir in (("untraced", None), ("traced", tdir)):
            wall, calls = _window(cell, store, seed, seconds, first,
                                  trace_dir)
            first += calls
            win = harness.Window(
                setup_s=0.0, window_s=wall, calls=calls, steps=steps,
                lanes=lanes, dt_ms=float(cfg["model"]["lif"]["dt"]),
                chips=cell.chips)
            rates[name] = {spec["name"]: reader.read(win)
                           for spec, reader in cell.end_to_end
                           if spec["name"] != "setup_s"}
        data = load(tdir, programs)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    red = reduce(data, cell.chips, calls * steps, calls)
    suffix = ".trials" if lanes > 1 else ""
    out = {"workload": cell.name, "seed": seed,
           "device": jax.devices()[0].device_kind, "windows": rates,
           "calls_traced": calls}
    if red is not None:
        out.update(metrics=red.metrics(suffix),
                   device_ms_per_step=red.busy,
                   named_share=red.named_share, layers=red.layers,
                   delivery_parts=red.parts, idle_ms_per_call=red.idle)
    if fixture:
        # the second call's start: the host's work before its scan, the
        # first steps, and the end of the fetch before it
        starts = sorted(s for n, s, _ in data["host"] if n == "call")
        c1 = starts[min(1, len(starts) - 1)]
        with open(fixture, "w") as f:
            json.dump({"note": f"{cell.name}, seed {seed}, "
                       f"{jax.devices()[0].device_kind}: a cut of a traced "
                       f"window from 2 ms before a call's start to 10 ms "
                       f"after it (bench/layers.py --fixture)",
                       **cut(data, c1 - 2_000_000, c1 + 10_000_000)}, f)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", help="also write the JSON line to this file")
    ap.add_argument("--fixture", help="write a cut of the trace here")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import harness, program
    program.import_program()
    cell = harness.load_cell(args.workload)
    harness.enable_compile_cache()
    out = run(cell, args.seed, args.seconds, args.fixture)
    line = json.dumps(out)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())


__all__ = ["Layers", "OTHER", "cut", "layer_of", "load", "reduce", "run",
           "scope_names"]
