"""The ambient telemetry session and the tracing-span API.

A session (:func:`telemetry` context manager) carries an optional
:class:`~repro.obs.events.EventSink` and a
:class:`~repro.obs.metrics.MetricsRegistry`.  Instrumented code asks
:func:`active` once and no-ops when there is no session — the whole
layer costs one host annotation per span (and adds zero device
operations) unless the caller opted in.

:class:`span` is the phase-tracing primitive: monotonic clock, nesting
(per-thread depth stacks, so concurrently supervised runs never corrupt
each other), and on exit one ``span`` event plus a
``phase.<name>`` observation in the registry.  Every span, with or
without a session, also lands in a running ``jax.profiler`` trace as the
host annotation ``repro.<name>``.
"""

from __future__ import annotations

import contextlib
import time
from contextvars import ContextVar
from threading import local
from typing import Optional

from .events import _jsonable, coerce_sink
from .metrics import MetricsRegistry

_ACTIVE: ContextVar[Optional["Telemetry"]] = ContextVar(
    "repro_obs_active", default=None)


class Telemetry:
    """One telemetry session: sink + metrics + session-relative clock."""

    def __init__(self, sink=None, metrics: Optional[MetricsRegistry] = None,
                 validate: bool = False):
        self.sink = coerce_sink(sink)
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.validate = validate
        self._t0 = time.monotonic()
        self._tls = local()    # per-thread span stacks

    def now(self) -> float:
        """Seconds since the session opened (monotonic)."""
        return time.monotonic() - self._t0

    def emit(self, type_: str, **fields) -> None:
        """Emit one event record (no-op without a sink; metrics still
        accumulate).  ``validate=True`` checks every record against
        ``schema.json`` before it is written — the tests' contract that
        the stream can never drift from the committed schema."""
        record = _jsonable({"t": round(self.now(), 6), "type": type_,
                            **fields})
        if self.validate:
            from .schema import validate_record
            errors = validate_record(record)
            if errors:
                raise ValueError(
                    f"invalid {type_!r} event: " + "; ".join(errors))
        if self.sink is not None:
            self.sink.emit(record)

    # -- span bookkeeping (per-thread) -------------------------------------

    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def close(self) -> None:
        if self.sink is not None:
            self.sink.close()


def active() -> Optional[Telemetry]:
    """The ambient session, or None — the one check every hook makes."""
    return _ACTIVE.get()


@contextlib.contextmanager
def telemetry(sink=None, *, metrics: Optional[MetricsRegistry] = None,
              validate: bool = False):
    """Open a telemetry session for the enclosed block.

        with obs.telemetry("run.jsonl"):
            simulate(c, cfg, t_steps, ...)

    ``sink`` is a path (JSONL file), a callable (one dict per event), an
    :class:`EventSink`, or None (metrics only).  The sink is closed —
    async writes joined, writer errors re-raised — when the block exits.
    """
    session = Telemetry(sink, metrics=metrics, validate=validate)
    token = _ACTIVE.set(session)
    try:
        yield session
    finally:
        _ACTIVE.reset(token)
        session.close()


class span:
    """Tracing span: ``with span("build", what="synapses"): ...``.

    Always opens a ``jax.profiler.TraceAnnotation`` named
    ``repro.<name>``, so a profiler trace shows the host's work on the
    same clock as the device's operations (the annotation costs about a
    microsecond when no profiler runs).  Without an active session that
    is all it does: no clock read, no event.  With one, on exit:
    ``wall_s`` is set on the span object, a ``span`` event is emitted,
    and ``phase.<name>`` is observed in the registry.
    """

    __slots__ = ("name", "attrs", "wall_s", "_session", "_start", "_depth",
                 "_annotation")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs
        self.wall_s: Optional[float] = None
        self._session: Optional[Telemetry] = None

    def __enter__(self):
        from jax.profiler import TraceAnnotation
        self._annotation = TraceAnnotation(f"repro.{self.name}")
        self._annotation.__enter__()
        s = active()
        if s is not None:
            self._session = s
            stack = s._stack()
            self._depth = len(stack)
            stack.append(self.name)
            self._start = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb):
        s = self._session
        if s is not None:
            self.wall_s = time.monotonic() - self._start
            s._stack().pop()
            s.metrics.observe(f"phase.{self.name}", self.wall_s)
            fields = {"name": self.name, "wall_s": round(self.wall_s, 6),
                      "depth": self._depth}
            if self.attrs:
                fields["attrs"] = self.attrs
            s.emit("span", **fields)
        self._annotation.__exit__(exc_type, exc, tb)
        return False


__all__ = ["Telemetry", "active", "span", "telemetry"]
