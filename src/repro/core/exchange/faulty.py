"""Fault-injection wrapper scheme: break the exchange layer on purpose.

Recovery machinery that is never exercised is decorative.  This scheme
wraps any registered exchange scheme and injects the three distributed
failure modes the resilience layer (:mod:`repro.core.health`) must
survive, without hardware and inside CI:

* **dropped payloads** (``drop_payload_at``): at configured steps the
  chosen partition's delayed spikes are zeroed *before* compaction — its
  whole outgoing fan-out silently vanishes from every partition's event
  list.  Only the exchange sees the zeroed vector: the step body hands
  ``deliver`` the partition's real delayed spikes, and the inner scheme's
  drop accounting compares that requested fan-out against the kept one,
  so the loss shows up exactly in the ``dropped`` counter (a lost message
  is a counted message; tests/test_health.py pins the count).
* **corrupt payloads** (``corrupt_payload_at``): the delayed-spike vector
  is rolled by one before compaction — wrong neuron ids enter the event
  list, the downstream signature of a corrupted routing table.
* **partition failure / stragglers** (``fail_at`` / ``straggle_at``):
  host-side, through the chunk driver's ``host_supervise`` hook —
  a configured step inside the upcoming chunk raises
  :class:`ExchangeFault` (once: the retry after recovery proceeds),
  or sleeps ``straggle_s`` seconds per configured straggle step.

Injection is data-driven: the fault step lists ride in the scheme state
as *traced* arrays, so reconfiguring steps never retraces.  The wrapper
delegates ``build`` / ``exchange`` / ``deliver`` to the inner scheme and
adds only the ``exchange_at`` step-aware hook the unified step body
(:mod:`repro.core.step`) consults.  Typical use::

    configure_faulty(inner="event", spec=FaultSpec(partition=1,
                                                   fail_at=(96,)))
    cfg = DistConfig(sim, scheme="faulty")
    run_resilient(lambda resume, cap: simulate_distributed(...), ...)
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .base import Topology, get_scheme, register_scheme


class ExchangeFault(RuntimeError):
    """Injected partition failure (host-side, from ``host_supervise``)."""


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """What to break, where, and when (step indices are global)."""

    partition: int = 0
    drop_payload_at: tuple = ()      # zero the partition's outgoing spikes
    corrupt_payload_at: tuple = ()   # roll its spike vector by one
    fail_at: tuple = ()              # raise ExchangeFault (host, once each)
    straggle_at: tuple = ()          # sleep straggle_s (host)
    straggle_s: float = 0.05


class FaultyState(NamedTuple):
    """Partition-stacked wrapper state: the inner scheme's state plus the
    fault plan as traced arrays (leaves all carry the leading P axis the
    distributed runners vmap/shard over)."""

    inner: Any
    part: jax.Array        # [P] int32, the faulty partition id (replicated)
    drop_at: jax.Array     # [P, Kd] int32 step ids (empty -> no injection)
    corrupt_at: jax.Array  # [P, Kc] int32 step ids


def _stacked_steps(steps, n_parts: int) -> jnp.ndarray:
    arr = np.asarray(sorted(steps), dtype=np.int32).reshape(1, -1)
    return jnp.asarray(np.broadcast_to(arr, (n_parts, arr.shape[1])))


@register_scheme
class FaultyExchange:
    """``scheme="faulty"``: the configured inner scheme plus injected
    faults.  Configure via :func:`configure_faulty` before building."""

    name = "faulty"

    def __init__(self):
        self._inner = "event"
        self._spec = FaultSpec()
        self._fired: set = set()

    # -- host-side configuration ------------------------------------------
    def configure(self, inner: str = "event",
                  spec: FaultSpec = FaultSpec()) -> "FaultyExchange":
        if inner in ("faulty", "local"):
            raise ValueError(f"cannot wrap the {inner!r} scheme")
        self._inner = inner
        self._spec = spec
        self._fired = set()
        # The inner-scheme choice is trace-time Python state on this
        # singleton: drop any compiled program that may have baked in the
        # previous choice (the fault *steps* are traced data and never
        # need this).
        try:
            from ..distributed import _run_emulated, _shard_map_fn
            _run_emulated.clear_cache()
            _shard_map_fn.cache_clear()
        except Exception:
            pass
        return self

    @property
    def scheme(self):
        return get_scheme(self._inner)

    # -- ExchangeScheme protocol ------------------------------------------
    def build(self, d, sim, cap) -> FaultyState:
        P_ = d.n_parts
        s = self._spec
        return FaultyState(
            inner=self.scheme.build(d, sim, cap),
            part=jnp.full((P_,), int(s.partition), jnp.int32),
            drop_at=_stacked_steps(s.drop_payload_at, P_),
            corrupt_at=_stacked_steps(s.corrupt_payload_at, P_))

    def init_stats(self) -> dict:
        return self.scheme.init_stats()

    def exchange(self, state: FaultyState, delayed, cap, topo: Topology):
        # t-free protocol entry (never taken: the step body prefers
        # exchange_at when present) — delegate clean.
        return self.scheme.exchange(state.inner, delayed, cap, topo)

    def exchange_at(self, state: FaultyState, delayed, cap,
                    topo: Topology, t):
        """Step-aware exchange: inject on the configured partition at the
        configured steps, then run the inner exchange on the (possibly
        sabotaged) spike vector."""
        on_me = jax.lax.axis_index(topo.axis) == state.part
        hit = lambda at: jnp.any(at == t) & on_me  # noqa: E731
        d = jnp.where(hit(state.drop_at), jnp.zeros_like(delayed), delayed)
        d = jnp.where(hit(state.corrupt_at), jnp.roll(d, 1), d)
        return self.scheme.exchange(state.inner, d, cap, topo)

    def deliver(self, state: FaultyState, payload, delayed, sim, cap,
                topo: Topology):
        return self.scheme.deliver(state.inner, payload, delayed, sim, cap,
                                   topo)

    # -- chunk-driver hook ------------------------------------------------
    def host_supervise(self, start: int, stop: int) -> None:
        """Called by :func:`repro.core.health.run_chunked` before each
        chunk ``[start, stop)``: sleep per straggle step, then raise for a
        configured failure step — once per step, so the supervisor's
        restarted attempt proceeds past it (a crash, not a poison)."""
        s = self._spec
        for t in s.straggle_at:
            if start <= t < stop:
                time.sleep(s.straggle_s)
        for t in s.fail_at:
            if start <= t < stop and t not in self._fired:
                self._fired.add(t)
                raise ExchangeFault(
                    f"injected failure of partition {s.partition} "
                    f"at step {t}")


def configure_faulty(inner: str = "event",
                     spec: FaultSpec = FaultSpec()) -> FaultyExchange:
    """Configure the registered ``faulty`` singleton and return it."""
    scheme = get_scheme("faulty")
    return scheme.configure(inner=inner, spec=spec)


__all__ = ["ExchangeFault", "FaultSpec", "FaultyExchange", "FaultyState",
           "configure_faulty"]
