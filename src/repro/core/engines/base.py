"""Delivery-engine protocol, registry, and pytree-state plumbing.

A *delivery engine* is one strategy for turning the delayed spike vector
into the per-neuron synaptic drive ``g`` (in integer weight units).  Each
engine lives in its own module under :mod:`repro.core.engines` and
registers a singleton instance at import time:

    @register
    class CsrEngine:
        name = "csr"
        def build(self, c, cfg) -> state: ...       # host -> device, once
        def deliver(self, state, spikes, cfg): ...  # per step, traced

``build`` runs once per :func:`repro.core.engine.simulate` call (or once
per benchmark when the caller passes ``syn=``) and returns a device-resident
state object; ``deliver`` is traced into the jitted simulation step and must
be pure jnp / Pallas.  ``deliver`` returns ``(g_units, dropped)`` where
``dropped`` counts synapse events lost to capacity limits (0 for exact
engines).

State objects are frozen dataclasses registered as JAX pytrees via
:func:`register_state`: array fields are pytree children (traced), fields
declared with ``static_field()`` are aux data (hashable, part of the jit
cache key).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Protocol, runtime_checkable

import jax

from ..connectome import Connectome


# --------------------------------------------------------------------------
# Pytree state helper
# --------------------------------------------------------------------------

def static_field(**kw):
    """Dataclass field stored as pytree aux data (shape/mode metadata)."""
    kw.setdefault("metadata", {})
    kw["metadata"] = {**kw["metadata"], "static": True}
    return dataclasses.field(**kw)


def register_state(cls):
    """Register a frozen dataclass as a pytree: arrays are children,
    ``static_field`` entries are hashable aux data (jit cache key)."""
    fields = dataclasses.fields(cls)
    dyn = tuple(f.name for f in fields if not f.metadata.get("static"))
    static = tuple(f.name for f in fields if f.metadata.get("static"))

    def flatten(s):
        return (tuple(getattr(s, f) for f in dyn),
                tuple(getattr(s, f) for f in static))

    def unflatten(aux, children):
        return cls(**dict(zip(dyn, children)), **dict(zip(static, aux)))

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)
    return cls


# --------------------------------------------------------------------------
# Protocol + registry
# --------------------------------------------------------------------------

@runtime_checkable
class DeliveryEngine(Protocol):
    """One synaptic-delivery strategy (see module docstring).

    Capability flag: an engine that sets ``integrates_lif = True`` fuses
    the LIF neuron update into delivery itself and must provide
    ``deliver_fused(state, spikes, lif, drive, cfg) -> (new_lif,
    spikes [n] bool, dropped i32)``.  The shared step body
    (:mod:`repro.core.step`) then calls ``deliver_fused`` *instead of*
    ``deliver`` + the separate LIF update — the flag is what guarantees
    integration happens exactly once per step (never zero, never twice).
    Engines without the attribute are unfused (the default).

    Optional counters: an engine that counts its own work defines
    ``init_stats() -> dict`` of zero scalar counters and
    ``deliver_with_stats(state, spikes, cfg) -> (g_units, dropped,
    stats)``; the scan accumulates them into ``SimResult.stats`` (the
    ``event`` engine's ``slot_chunks``).  ``deliver`` keeps its two
    values either way.
    """

    name: str

    def build(self, c: Connectome, cfg) -> Any:
        """Construct device-resident synaptic state (host work, runs once)."""
        ...

    def deliver(self, state: Any, spikes: jax.Array, cfg
                ) -> tuple[jax.Array, jax.Array]:
        """spikes [n] bool -> (g_units [n] f32, dropped scalar i32)."""
        ...


_REGISTRY: dict[str, DeliveryEngine] = {}


def register(cls):
    """Class decorator: instantiate and register a delivery engine."""
    inst = cls()
    if not getattr(inst, "name", None):
        raise ValueError(f"{cls.__name__} must define a non-empty .name")
    _REGISTRY[inst.name] = inst
    return cls

def get_engine(name: str) -> DeliveryEngine:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown engine {name!r}; available: {sorted(_REGISTRY)}"
        ) from None


def available_engines() -> list[str]:
    return sorted(_REGISTRY)


def engine_integrates_lif(name: str) -> bool:
    """True iff ``name``'s engine fuses the LIF update into delivery (the
    ``integrates_lif`` capability) — the one place exchange schemes ask
    whether the step body's separate LIF update must be skipped."""
    return bool(getattr(get_engine(name), "integrates_lif", False))


def engine_init_stats(name: str) -> dict:
    """Zero-initialized counters of ``name``'s engine ({} for engines
    that count nothing) — the monolithic carry's scheme stats."""
    init = getattr(get_engine(name), "init_stats", None)
    return init() if init is not None else {}


# --------------------------------------------------------------------------
# Shared build helpers
# --------------------------------------------------------------------------

def quantized_in_weights(c: Connectome, cfg):
    """Target-major weights with the config's optional 9-bit cap applied."""
    from ..compress import quantize_weights
    w = c.in_weights
    if cfg.quantize_bits is not None:
        w = quantize_weights(w, cfg.quantize_bits)
    return w
