"""Telemetry layer (PR 7 acceptance): tracing spans, compile-cache
metrics, streamed JSONL events, and the run-report CLI.

Pins: (a) telemetry is bit-neutral — raster/records/state digests are
identical with a session active or not, on float32 AND Q19.12,
monolithic and distributed (P=4 emulate); (b) every emitted record
validates against the committed ``schema.json`` (enforced live via
``validate=True`` and again offline via ``validate_stream``); (c) the
chunk event stream is exactly ceil(T/K) records whose steps sum to T;
(d) spans nest, time, and no-op without a session; (e) the
compile-cache wrapper counts hits/misses per signature, dispatches
bit-identically, and falls back (permanently, flagged) when AOT
compilation is impossible; (f) checkpoint / health / restart /
escalation events fire at the supervision points that produced them;
(g) the report CLI renders a non-empty summary from any valid stream.
"""

import json
import math
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import (CapacityConfig, HealthConfig, SimConfig,
                        run_resilient, simulate, synthetic_flywire)
from repro.core.dcsr import build_dcsr
from repro.core.distributed import DistConfig, simulate_distributed
from repro.core.partition import even_partition
from repro.exp import ProbeSpec
from repro.obs.report import summarize
from repro.obs.schema import validate_record, validate_stream


@pytest.fixture(scope="module")
def setup():
    c = synthetic_flywire(n=400, target_synapses=8_000, seed=0)
    sugar = np.arange(80)
    d = build_dcsr(c, even_partition(c, 4))
    return c, sugar, d


PROBES = ProbeSpec(raster=True, pop_rate=True)


def _run(c, cfg, t, sugar, **kw):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return simulate(c, cfg, t, sugar_neurons=sugar, seed=3,
                        probes=PROBES, **kw)


def _run_dist(d, dcfg, t, sugar, **kw):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return simulate_distributed(d, dcfg, t, sugar_neurons=sugar, seed=3,
                                    emulate=True, probes=PROBES, **kw)


def _assert_bitwise(a, b):
    assert np.array_equal(np.asarray(a.counts), np.asarray(b.counts))
    assert np.array_equal(np.asarray(a.raster), np.asarray(b.raster))
    for k in a.records:
        assert np.array_equal(np.asarray(a.records[k]),
                              np.asarray(b.records[k])), k
    assert np.array_equal(np.asarray(a.state.v), np.asarray(b.state.v))
    assert int(np.asarray(a.dropped).sum()) == int(np.asarray(b.dropped).sum())


# --------------------------------------------------------------------------
# (a) telemetry is bit-neutral
# --------------------------------------------------------------------------

@pytest.mark.parametrize("engine,fx", [("csr", False), ("event", False),
                                       ("event", True)])
def test_telemetry_bit_identity_monolithic(setup, engine, fx):
    """With a session active, simulate() routes through the chunk driver;
    the results must stay bitwise what the bare monolithic scan makes."""
    c, sugar, _ = setup
    cfg = SimConfig(engine=engine, fixed_point=fx)
    ref = _run(c, cfg, 50, sugar)
    with obs.telemetry(validate=True):
        tele = _run(c, cfg, 50, sugar)
        tele_chunked = _run(c, cfg, 50, sugar, chunk_steps=16)
    _assert_bitwise(ref, tele)
    _assert_bitwise(ref, tele_chunked)


def test_telemetry_bit_identity_distributed(setup):
    c, sugar, d = setup
    dcfg = DistConfig(sim=SimConfig(engine="event"), scheme="event")
    ref = _run_dist(d, dcfg, 50, sugar)
    with obs.telemetry(validate=True):
        tele = _run_dist(d, dcfg, 50, sugar)
    _assert_bitwise(ref, tele)


def _spy(monkeypatch, module, attr):
    """Record the compile-cache signature of every call to the
    ``InstrumentedJit`` at ``module.attr``."""
    real = getattr(module, attr)
    seen = []

    def spy(*args):
        seen.append(real._sig_id(real._signature(args)))
        return real(*args)

    monkeypatch.setattr(module, attr, spy)
    return seen


def _session_signatures(tele, fn):
    return [r["signature"] for r in tele.metrics.compile_snapshot()[
        "signatures"] if r["fn"] == fn]


def test_session_runs_the_plain_program(setup, monkeypatch):
    """One unchunked simulate() under a session compiles and runs the very
    program the session-less call runs (no step offset), still as one
    chunk of the event stream."""
    from repro.core import engine
    c, sugar, _ = setup
    cfg = SimConfig(engine="event", fixed_point=True)
    seen = _spy(monkeypatch, engine, "_run_scan")
    # 47 steps: a signature no other session of this module compiled
    ref = _run(c, cfg, 47, sugar)
    events = []
    with obs.telemetry(events.append, validate=True) as tele:
        got = _run(c, cfg, 47, sugar)
        sigs = _session_signatures(tele, "engine.run_scan")
    assert len(seen) == 2 and seen[0] == seen[1]
    assert sigs == [seen[0]]
    assert [e["steps"] for e in events if e["type"] == "chunk"] == [47]
    _assert_bitwise(ref, got)


def test_session_runs_the_plain_program_distributed(setup, monkeypatch):
    from repro.core import distributed
    c, sugar, d = setup
    dcfg = DistConfig(sim=SimConfig(engine="event"), scheme="event")
    seen = _spy(monkeypatch, distributed, "_run_emulated")
    ref = _run_dist(d, dcfg, 46, sugar)
    events = []
    with obs.telemetry(events.append, validate=True) as tele:
        got = _run_dist(d, dcfg, 46, sugar)
        sigs = _session_signatures(tele, "distributed.run_emulated")
        _run_dist(d, dcfg, 46, sugar, chunk_steps=23)   # offset program
    assert len(seen) == 4 and seen[0] == seen[1] != seen[2] == seen[3]
    assert sigs == [seen[0]]
    assert [e["steps"] for e in events if e["type"] == "chunk"] == [46, 23,
                                                                    23]
    _assert_bitwise(ref, got)


def test_compile_cache_in_stats_only_with_session(setup):
    c, sugar, _ = setup
    cfg = SimConfig(engine="csr")
    assert "compile_cache" not in _run(c, cfg, 10, sugar).stats
    with obs.telemetry():
        cc = _run(c, cfg, 10, sugar).stats["compile_cache"]
    assert set(cc) == {"hits", "misses", "signatures"}
    assert cc["misses"] >= 1


# --------------------------------------------------------------------------
# (b)+(c) event stream: schema-valid, chunk arithmetic exact
# --------------------------------------------------------------------------

def test_event_stream_schema_and_chunks(setup, tmp_path):
    c, sugar, _ = setup
    path = tmp_path / "run.jsonl"
    # K=13 -> signatures fresh in this process, so compile events appear
    t_steps, K = 50, 13
    with obs.telemetry(str(path), validate=True):
        _run(c, SimConfig(engine="event"), t_steps, sugar, chunk_steps=K)
    assert validate_stream(str(path)) == []
    events = [json.loads(l) for l in path.read_text().splitlines()]
    kinds = {e["type"] for e in events}
    assert {"run_start", "chunk", "span", "compile", "run_end"} <= kinds
    chunks = [e for e in events if e["type"] == "chunk"]
    assert len(chunks) == math.ceil(t_steps / K)
    assert sum(e["steps"] for e in chunks) == t_steps
    assert [e["step"] for e in chunks] == [13, 26, 39, 50]
    # cumulative counters are monotone; deltas reconcile exactly
    prev = 0
    for e in chunks:
        assert e["counters"]["spikes"] - prev == e["delta"]["spikes"]
        prev = e["counters"]["spikes"]
    # the t clock is monotone across the stream
    ts = [e["t"] for e in events]
    assert ts == sorted(ts)
    start = next(e for e in events if e["type"] == "run_start")
    assert start["kind"] == "simulate" and start["n"] == c.n
    end = next(e for e in events if e["type"] == "run_end")
    assert end["steps"] == t_steps
    assert end["counters"]["spikes"] == chunks[-1]["counters"]["spikes"]


def test_distributed_event_stream(setup, tmp_path):
    c, sugar, d = setup
    path = tmp_path / "dist.jsonl"
    with obs.telemetry(str(path), validate=True):
        _run_dist(d, DistConfig(sim=SimConfig(engine="event"),
                                scheme="event"), 30, sugar, chunk_steps=10)
    assert validate_stream(str(path)) == []
    events = [json.loads(l) for l in path.read_text().splitlines()]
    start = next(e for e in events if e["type"] == "run_start")
    assert start["kind"] == "simulate_distributed"
    assert start["scheme"] == "event"
    assert len([e for e in events if e["type"] == "chunk"]) == 3


def test_checkpoint_events(setup, tmp_path):
    c, sugar, _ = setup
    path = tmp_path / "run.jsonl"
    with obs.telemetry(str(path), validate=True):
        _run(c, SimConfig(engine="csr"), 40, sugar, chunk_steps=10,
             checkpoint_dir=str(tmp_path / "ckpt"))
    events = [json.loads(l) for l in path.read_text().splitlines()]
    ckpts = [e for e in events if e["type"] == "checkpoint"]
    assert [e["step"] for e in ckpts] == [10, 20, 30, 40]
    assert all(e["async_save"] is False for e in ckpts)


def test_health_breach_event(setup, tmp_path):
    c, sugar, _ = setup
    path = tmp_path / "run.jsonl"
    cfg = SimConfig(engine="csr",
                    health=HealthConfig(rate_lo_hz=1e9))   # trips chunk 1
    with obs.telemetry(str(path), validate=True):
        with pytest.raises(Exception, match="rate_envelope"):
            _run(c, cfg, 40, sugar, chunk_steps=10)
    assert validate_stream(str(path)) == []
    events = [json.loads(l) for l in path.read_text().splitlines()]
    [breach] = [e for e in events if e["type"] == "health"]
    assert breach["kind"] == "rate_envelope" and breach["step"] == 10


def test_restart_event_from_run_resilient(tmp_path):
    path = tmp_path / "run.jsonl"
    calls = []

    def run_fn(resume, capacity):
        calls.append(resume)
        if len(calls) == 1:
            raise RuntimeError("injected crash")
        return "done"

    with obs.telemetry(str(path), validate=True):
        assert run_resilient(run_fn) == "done"
    events = [json.loads(l) for l in path.read_text().splitlines()]
    [restart] = [e for e in events if e["type"] == "restart"]
    assert restart["attempt"] == 1 and restart["error"] == "RuntimeError"
    assert restart["resume_step"] is None          # no checkpoint_dir
    assert any(e["type"] == "span" and e["name"] == "run_resilient"
               for e in events)


def test_escalation_event_from_run_resilient(tmp_path):
    from repro.core.health import SimulationHealthError
    path = tmp_path / "run.jsonl"
    calls = []

    def run_fn(resume, capacity):
        calls.append(capacity)
        if len(calls) == 1:
            raise SimulationHealthError("drop_rate", 10, 3.5, 1.0)
        return capacity

    with obs.telemetry(str(path), validate=True):
        cap = run_resilient(run_fn, capacity=CapacityConfig(
            spike_capacity=8, syn_budget=64, block_capacity=8))
    assert cap.spike_capacity > 8                  # escalated
    events = [json.loads(l) for l in path.read_text().splitlines()]
    [esc] = [e for e in events if e["type"] == "escalation"]
    assert esc["attempt"] == 1 and esc["kind"] == "drop_rate"


# --------------------------------------------------------------------------
# (d) spans
# --------------------------------------------------------------------------

def test_span_noop_without_session():
    with obs.span("anything", extra=1) as sp:
        pass
    assert sp.wall_s is None
    assert obs.active() is None


def _host_annotations(trace_dir) -> set:
    from glob import glob

    from jax.profiler import ProfileData
    [path] = glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    pd = ProfileData.from_file(path)
    return {e.name for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}


def test_span_writes_profiler_annotation(tmp_path):
    """Every span lands in a running profiler trace as ``repro.<name>``,
    with a telemetry session and without one."""
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("alone") as sp:
            pass
        with obs.telemetry():
            with obs.span("in_session"):
                pass
    assert sp.wall_s is None
    assert {"repro.alone", "repro.in_session"} <= _host_annotations(tmp_path)


def test_hot_path_spans_in_profiler_trace(setup, tmp_path):
    """The host's work on the hot path is named in the trace: the
    scenario draw, the carry init and the dispatch of the scan, for single
    runs, trial batches and partitioned runs."""
    from repro.exp import build_scenario, run_trials
    c, sugar, d = setup
    cfg = SimConfig(engine="event")
    dcfg = DistConfig(sim=cfg, scheme="event")
    with jax.profiler.trace(str(tmp_path)):
        stim = build_scenario("sugar_feeding", c, cfg, n_sugar=20)
        jax.block_until_ready(simulate(c, cfg, 10, stimulus=stim).counts)
        jax.block_until_ready(run_trials(c, cfg, 10, seeds=2,
                                         stimulus=stim).counts)
        _run_dist(d, dcfg, 10, sugar)
    names = _host_annotations(tmp_path)
    assert {"repro.scenario", "repro.simulate", "repro.init_carry",
            "repro.dispatch", "repro.run_trials",
            "repro.simulate_distributed"} <= names


def test_span_nesting_depth_and_metrics():
    got = []
    with obs.telemetry(got.append) as tele:
        with obs.span("outer"):
            with obs.span("inner", tag="x") as sp:
                pass
        assert sp.wall_s is not None and sp.wall_s >= 0
        o = tele.metrics.observations()
        assert o["phase.outer"]["count"] == 1
        assert o["phase.inner"]["count"] == 1
    by_name = {e["name"]: e for e in got if e["type"] == "span"}
    assert by_name["inner"]["depth"] == 1
    assert by_name["outer"]["depth"] == 0
    assert by_name["inner"]["attrs"] == {"tag": "x"}
    # inner closes before outer -> emitted first
    names = [e["name"] for e in got if e["type"] == "span"]
    assert names.index("inner") < names.index("outer")


def test_telemetry_session_scoping():
    with obs.telemetry() as tele:
        assert obs.active() is tele
        with obs.telemetry() as inner:
            assert obs.active() is inner
        assert obs.active() is tele
    assert obs.active() is None


# --------------------------------------------------------------------------
# (e) compile-cache wrapper
# --------------------------------------------------------------------------

def test_instrumented_jit_hit_miss_and_identity():
    base = jax.jit(lambda x, k: x * k, static_argnums=(1,))
    wrapped = obs.InstrumentedJit(base, "test.mul", static_argnums=(1,))
    x = jnp.arange(8.0)
    plain = wrapped(x, 3)                      # no session: passthrough
    with obs.telemetry(validate=True) as tele:
        a = wrapped(x, 3)                      # miss -> AOT compile
        b = wrapped(x + 1, 3)                  # same signature -> hit
        wrapped(x, 4)                          # new static -> miss
        wrapped(jnp.arange(4.0), 3)            # new shape -> miss
        cc = tele.metrics.compile_snapshot()
    assert np.array_equal(np.asarray(a), np.asarray(plain))
    assert np.array_equal(np.asarray(b), np.asarray(x * 3 + 3))
    assert cc["misses"] == 3 and cc["hits"] == 1
    assert len(cc["signatures"]) == 3
    sigs = {r["signature"] for r in cc["signatures"]}
    assert len(sigs) == 3
    assert all(not r["fallback"] for r in cc["signatures"])


def test_instrumented_jit_fallback_never_breaks_the_call():
    calls = []

    class NotLowerable:
        def __call__(self, x):
            calls.append("plain")
            return x + 1
        # .lower is missing -> AttributeError -> permanent fallback

    wrapped = obs.InstrumentedJit(NotLowerable(), "test.fallback")
    with obs.telemetry(validate=True) as tele:
        out = wrapped(jnp.float32(1.0))
        wrapped(jnp.float32(2.0))
        cc = tele.metrics.compile_snapshot()
    assert float(out) == 2.0
    assert calls == ["plain", "plain"]
    assert cc["misses"] == 1 and cc["hits"] == 1
    [rec] = cc["signatures"]
    assert rec["fallback"] is True


# --------------------------------------------------------------------------
# sinks
# --------------------------------------------------------------------------

def test_jsonl_sink_async_close_flushes(tmp_path):
    path = tmp_path / "s.jsonl"
    sink = obs.JsonlSink(str(path), async_flush=True)
    for i in range(100):
        sink.emit({"t": float(i), "type": "span", "name": "x",
                   "wall_s": 0.0, "depth": 0})
    sink.close()
    lines = path.read_text().splitlines()
    assert len(lines) == 100
    assert json.loads(lines[99])["t"] == 99.0
    sink.close()                                   # idempotent


def test_jsonl_sink_write_error_surfaces_at_close(tmp_path):
    path = tmp_path / "s.jsonl"
    sink = obs.JsonlSink(str(path), async_flush=True)
    sink._file.close()                             # force the writer to fail
    sink.emit({"t": 0.0, "type": "span"})
    with pytest.raises(ValueError):
        sink.close()


def test_jsonable_coercion(tmp_path):
    path = tmp_path / "s.jsonl"
    with obs.telemetry(str(path)):
        obs.active().emit("checkpoint", step=np.int64(7), async_save=False)
    rec = json.loads(path.read_text())
    assert rec["step"] == 7 and isinstance(rec["step"], int)


# --------------------------------------------------------------------------
# schema validator
# --------------------------------------------------------------------------

def test_validate_record_rejects_bad_records():
    assert validate_record({"type": "chunk"})              # missing t
    assert validate_record({"t": 0.0, "type": "nope"})     # unknown type
    assert validate_record({"t": 0.0, "type": "chunk", "step": 1})
    # bool must not satisfy integer/number
    bad = validate_record({"t": 0.0, "type": "checkpoint", "step": True})
    assert any("expected integer" in e for e in bad)
    ok = {"t": 0.0, "type": "chunk", "step": 16, "steps": 16,
          "wall_s": 0.1, "steps_per_s": 160.0,
          "counters": {"spikes": 3}, "delta": {"spikes": 3}}
    assert validate_record(ok) == []
    bad = dict(ok, counters={"spikes": "three"})
    assert any("counters" in e for e in validate_record(bad))


def test_validate_stream_empty_is_error(tmp_path):
    p = tmp_path / "e.jsonl"
    p.write_text("")
    assert validate_stream(str(p))


# --------------------------------------------------------------------------
# (g) report CLI
# --------------------------------------------------------------------------

def test_report_renders_real_stream(setup, tmp_path, capsys):
    c, sugar, _ = setup
    path = tmp_path / "run.jsonl"
    with obs.telemetry(str(path), validate=True):
        _run(c, SimConfig(engine="event"), 50, sugar, chunk_steps=16)
    events = [json.loads(l) for l in path.read_text().splitlines()]
    text = summarize(events)
    assert "run: simulate (event)" in text
    assert "throughput: 50 steps" in text
    assert "phases (spans):" in text
    assert "compile cache:" in text
    from repro.obs.report import main
    assert main([str(path)]) == 0
    assert capsys.readouterr().out.strip()


def test_report_exit_codes(tmp_path, capsys):
    from repro.obs.report import main
    p = tmp_path / "empty.jsonl"
    p.write_text("")
    assert main([str(p)]) == 1
    from repro.obs.check import main as check_main
    good = tmp_path / "ok.jsonl"
    good.write_text(json.dumps({"t": 0.0, "type": "span", "name": "x",
                                "wall_s": 0.0, "depth": 0}) + "\n")
    assert check_main([str(good)]) == 0
    assert check_main([str(p), str(good)]) == 1
