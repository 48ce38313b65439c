"""One run of one benchmark cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<name>.json``); the traffic names the entry point
(``bench/entries/<name>.py``) and the configuration its plain reference
(``bench/references/<name>.py``).  End-to-end metrics are read by
``bench/end_to_end/<metric>.py`` and per-layer metrics by
``bench/metrics/<metric>.py``, each found by its name in ``BENCHMARK.json``.

A run: find the chips (or exit non-zero with no result), make the
connectome on the device from ``--seed``, build the entry's state, warm up
one call of the cell's shape (all of this is ``setup_s``), then run whole
calls until ``--seconds`` have passed; the window ends with the last call,
whose counts and state are on the host.  Call ``i`` draws its stimulus and
RNG streams from ``(--seed, i)``.  With ``--trace 1`` the window runs under
the profiler and the per-layer metrics are reduced from its trace.  After
the window, one call drawn from the seed is simulated again by the plain
reference and compared (``bench/compare.py``); the numbers compared and
their limits are the last lines on standard error and the last key of the
result, the one JSON line on standard output.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from types import ModuleType

import numpy as np

from bench import netgen, program
from bench.compare import compared

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
COMPILE_CACHE = os.path.join(BENCH, ".cache", "jax")
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


class NoChip(SystemExit):
    """No TPU, or fewer chips than the cell asks for."""


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(kind: str, name: str) -> ModuleType:
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(f"bench.{kind}.{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    entry: ModuleType
    reference: ModuleType
    end_to_end: list        # [(spec, reader module)] this cell reports
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    """Resolve a cell of ``BENCHMARK.json`` and every file it names."""
    bench = _load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: unknown workload {name!r}; "
                         f"known: {sorted(cells)}")
    return cell_from(cells[name], bench)


def cell_from(w: dict, bench: dict) -> Cell:
    """Resolve the files a workload entry ``w`` names; its metrics are
    those of ``bench`` that it reports."""
    name = w["name"]
    config = _load_json(os.path.join(BENCH, "configs", f"{w['config']}.json"))
    traffic = _load_json(os.path.join(BENCH, "traffic",
                                      f"{w['traffic']}.json"))
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        entry=_load_module("entries", traffic["entry"]),
        reference=_load_module("references", config["reference"]),
        end_to_end=[(m, _load_module("end_to_end", m["name"]))
                    for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[(m, _load_module("metrics", m["name"]))
                   for m in bench["per_layer"] if _reports(m, name)])


# --------------------------------------------------------------------------
# Seeds: every stream of a run follows from --seed
# --------------------------------------------------------------------------

def _entropy(seed: int, *path: int) -> list[int]:
    return [int(seed) & (2 ** 64 - 1), *path]


def _seeds(seed: int, path: tuple, k: int) -> list[int]:
    st = np.random.SeedSequence(_entropy(seed, *path)).generate_state(k)
    return [int(x) & 0x7FFFFFFF for x in st]


def network_seed(seed: int) -> int:
    return _seeds(seed, (0,), 1)[0]


def call_seeds(seed: int, i: int, lanes: int) -> tuple[int, list[int]]:
    """Call ``i``'s stimulus seed and one RNG seed per lane (``i = -1`` is
    the warm-up call)."""
    s = _seeds(seed, (1, i + 1), lanes + 1)
    return s[0], s[1:]


def sample_call(seed: int, n_calls: int) -> int:
    """The call the reference checks, drawn from the seed."""
    return int(np.random.default_rng(_entropy(seed, 3)).integers(n_calls))


# --------------------------------------------------------------------------
# Devices, compile counting
# --------------------------------------------------------------------------

def require_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"bench: no TPU found (JAX platform "
                     f"{devs[0].platform!r}); the benchmark never runs on "
                     f"the CPU")
    if len(devs) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips, JAX found "
                     f"{len(devs)}")
    return devs


@contextlib.contextmanager
def compilations():
    """Yields the list of programs traced or compiled (or loaded from the
    persistent cache) inside the block, by function name."""
    import jax.monitoring
    names: list[str] = []

    def listen(event: str, duration: float, **kw) -> None:
        if event in COMPILE_EVENTS:
            names.append(kw.get("fun_name", event))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield names
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


@dataclasses.dataclass(frozen=True)
class Window:
    """What the end-to-end readers see."""

    setup_s: float
    window_s: float
    calls: int
    steps: int        # per call
    lanes: int
    dt_ms: float
    chips: int


def _events_delivered(answers, fan_out: np.ndarray) -> int:
    """Synapse events the window's spikes deliver: sum of count x fan-out."""
    return int(sum(int((a.counts.astype(np.int64) @ fan_out).sum())
                   for a in answers))


def _say(*parts) -> None:
    print("[bench]", *parts, file=sys.stderr, flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, require_tpu: bool = True) -> dict:
    """One run; returns the result line's object."""
    import jax
    devices = (require_devices(cell.chips) if require_tpu
               else jax.devices())[: cell.chips]
    cfg, tr = cell.config, cell.traffic
    steps, lanes = int(tr["steps"]), int(tr["lanes"])
    dt_ms = float(cfg["model"]["lif"]["dt"])

    marks = [("start", t_start), ("devices", time.monotonic())]
    with jax.profiler.TraceAnnotation("setup"):
        net = netgen.generate(cfg["network"], network_seed(seed))
        conn = program.connectome(net)
        marks.append(("network", time.monotonic()))
        store = cell.entry.build(conn, cfg, tr)
        marks.append(("build", time.monotonic()))
        with jax.profiler.TraceAnnotation("warmup"):
            cell.entry.fetch(cell.entry.call(store, *call_seeds(seed, -1,
                                                                lanes)))
        marks.append(("warmup", time.monotonic()))
    setup_s = marks[-1][1] - t_start
    _say(f"setup {setup_s:.3f} s (" + ", ".join(
        f"{b[0]} {b[1] - a[1]:.2f} s" for a, b in zip(marks, marks[1:]))
        + f"): network n={net.n} synapses={net.nnz}, seed "
        f"{network_seed(seed)}")

    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    answers = []
    with compilations() as compiled, (jax.profiler.trace(tdir) if trace
                                      else contextlib.nullcontext()):
        with jax.profiler.TraceAnnotation("window"):
            t0 = time.monotonic()
            while True:
                with jax.profiler.TraceAnnotation("call"):
                    r = cell.entry.call(store,
                                        *call_seeds(seed, len(answers), lanes))
                with jax.profiler.TraceAnnotation("fetch"):
                    answers.append(cell.entry.fetch(r))
                if time.monotonic() - t0 >= seconds:
                    break
            window_s = time.monotonic() - t0
    peak = memory_peak(devices)
    del r, store
    gc.collect()
    if compiled:
        raise SystemExit(f"bench: {len(compiled)} compilation(s) inside the "
                         f"window ({sorted(set(compiled))}): the warm-up "
                         f"missed a shape")
    events = _events_delivered(answers, np.diff(net.out_indptr))
    n_steps = len(answers) * steps
    _say(f"window {window_s:.3f} s: {len(answers)} calls x {steps} steps x "
         f"{lanes} lanes, compilations in window 0")
    _say(f"synapse events delivered per step {events / n_steps:.1f} "
         f"(sum of count x fan-out over {n_steps} steps, all lanes); "
         f"spikes per call {[int(a.counts.sum()) for a in answers]}; "
         f"dropped {[a.dropped.tolist() for a in answers]}")

    result = {"correct": False, "attempted": len(answers), "failed": 0,
              "metrics": {}, "device": {
                  "platform": devices[0].platform,
                  "kind": devices[0].device_kind,
                  "count": len(jax.devices()),
                  "memory_peak_bytes": peak}}
    win = Window(setup_s=setup_s, window_s=window_s, calls=len(answers),
                 steps=steps, lanes=lanes, dt_ms=dt_ms, chips=cell.chips)
    if trace:
        from bench import devtrace, work
        red = devtrace.reduce(devtrace.load(tdir), cell.chips)
        shutil.rmtree(tdir, ignore_errors=True)
        measure = work.Measure.of(red, win, cfg, events, devices[0])
        for spec, reader in cell.per_layer:
            value = reader.read(measure)
            if value is not None:
                result["metrics"][spec["name"]] = {"value": value,
                                                   "unit": spec["unit"]}
        result["device"]["busy_s"] = red.busy_s
        result["device"]["window_s"] = red.window_s
        result["breakdown"] = red.breakdown()
    else:
        for spec, reader in cell.end_to_end:
            result["metrics"][spec["name"]] = {"value": reader.read(win),
                                               "unit": spec["unit"]}

    j = sample_call(seed, len(answers))
    stim_seed, lane_seeds = call_seeds(seed, j, lanes)
    t_ref = time.monotonic()
    want = cell.reference.run_call(net, cfg["model"], tr, lane_seeds,
                                   stim_seed, layout=cfg.get("partition"))
    limits = cfg["limits"]
    checks = {k: {"value": v, "limit": limits[k]}
              for k, v in compared(answers[j], want, cfg["model"]).items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    _say(f"reference: call {j} of {len(answers)}, lanes {lane_seeds}, "
         f"{time.monotonic() - t_ref:.1f} s")
    result["correct"] = correct
    result["failed"] = 0 if correct else 1
    result["checks"] = checks
    for k, c in checks.items():
        _say(f"check {k} {c['value']} limit {c['limit']}")
    return result


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at the checkout's fixed
    ``bench/.cache/jax``, handed to the simulator's own cache switch, and
    holding every program (not only those that compile for a second)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = COMPILE_CACHE
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable
    jax.config.update("jax_compilation_cache_dir", enable())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(t_start: float, argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    program.import_program()
    cell = load_cell(args.workload)
    enable_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start)
    print(json.dumps(result), flush=True)
    return 0


__all__ = ["Cell", "NoChip", "Window", "call_seeds", "cell_from",
           "enable_compile_cache",
           "load_cell", "main", "network_seed", "require_devices",
           "run_cell", "sample_call"]
