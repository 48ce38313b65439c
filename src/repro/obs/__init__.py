"""Runtime telemetry for the simulator (docs/observability.md).

The paper's validation is statistical and its headline claim is
throughput — so a run must be able to answer, *while it runs*, where
wall-clock goes (build vs trace vs compile vs execute), whether the
per-signature compiled-program cache is hitting, and how a long chunked
simulation is progressing.  This package is that answer, and it is
strictly off-path: **zero extra device operations when no telemetry
session is active** (every hook checks :func:`active` once and
no-ops), and with telemetry on, all instrumentation happens host-side
at chunk/run granularity — O(1) per chunk, never O(n) per step — so
raster/state results are bit-identical with telemetry on or off
(pinned in tests/test_obs.py).

Pieces:

* :func:`telemetry` / :func:`active` / :class:`Telemetry` — the ambient
  session: an optional :class:`EventSink` (JSONL file, callback) plus a
  :class:`MetricsRegistry`.
* :class:`span` — nested, thread-safe, monotonic-clock tracing spans
  (``span("build")`` / ``span("compile")`` / ``span("chunk")``) wired
  through ``simulate``, ``simulate_distributed``, ``run_trials``,
  ``run_resilient`` and the host-side builds; each also writes a
  ``repro.<name>`` annotation into a running ``jax.profiler`` trace.
* :mod:`repro.obs.scopes` — the names of the step's layers on the
  device (``jax.named_scope`` in the step body), so a profiler trace
  splits device time by layer.
* :class:`InstrumentedJit` — per-signature compile-cache metrics
  (hit/miss counters, trace+compile wall time, ``cost_analysis()``
  FLOPs/bytes) around the jitted scan entry points.
* :class:`JsonlSink` — async-flushed streamed events, one record per
  chunk boundary, validated by ``schema.json``
  (``python -m repro.obs.check run.jsonl``).
* ``python -m repro.obs.report run.jsonl`` — phase/throughput/health
  summary of any event stream.
* :func:`profile_trace` — ``jax.profiler.trace`` gating for the
  launcher's ``--profile DIR``.
"""

from .events import CallbackSink, EventSink, JsonlSink
from .jit import InstrumentedJit
from .metrics import MetricsRegistry
from .profiler import profile_trace
from .schema import validate_record
from .trace import Telemetry, active, span, telemetry

__all__ = [
    "CallbackSink",
    "EventSink",
    "InstrumentedJit",
    "JsonlSink",
    "MetricsRegistry",
    "Telemetry",
    "active",
    "profile_trace",
    "span",
    "telemetry",
    "validate_record",
]
