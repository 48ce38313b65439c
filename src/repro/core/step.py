"""The one partition-aware simulation step (paper §3.2.2-3.2.3).

The paper's central claim is that one model of computation — sparse event
exchange between self-contained cores — spans a single Loihi core and 12
chips.  This module is that claim rendered as code: exactly one step body
— ring-buffer delayed-spike readout, spike exchange/delivery, stimulus
step, LIF integration, pad masking, counters, probe collection — shared
verbatim by ``simulate()`` (the degenerate P=1 ``local`` scheme, no
collectives) and ``simulate_distributed()`` (any multi-partition scheme
under vmap emulation or shard_map).  What varies is *only* the registered
:class:`repro.core.exchange.ExchangeScheme` and the
:class:`~repro.core.exchange.base.Topology` it runs over.

One structural exception, negotiated through a capability flag rather
than a second step body: a scheme whose delivery already *integrates*
(the fused delivery->LIF Pallas kernel, ``engine="blocked_fused"``)
reports ``fuses_lif(sim) == True`` and the step calls its
``deliver_fused`` instead of ``deliver`` + ``apply_drive`` — delivery
and integration happen in one kernel and the step body must not
integrate again.  Everything around that call (ring buffer, stimulus,
pad masking, counters, probes) is still the one shared body.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.obs import scopes

from .exchange.base import ExchangeScheme, Topology
from .health import health_step_stats
from .neuron import LIFState


def _scheme_fuses_lif(scheme: ExchangeScheme, sim) -> bool:
    """Trace-time capability check: does this (scheme, config) pair fuse
    the LIF update into delivery?  Schemes without the hook are unfused —
    the step body then owns the one and only LIF update."""
    fuses = getattr(scheme, "fuses_lif", None)
    return bool(fuses(sim)) if fuses is not None else False


class SimCarry(NamedTuple):
    """Per-partition scan carry (leaves are [U]-shaped; U = n when P = 1)."""
    lif: LIFState
    ring: jax.Array        # [D, U] bool delayed-spike ring buffer
    ptr: jax.Array         # scalar int32
    key: jax.Array
    counts: jax.Array      # [U] int32 spike counts
    dropped: jax.Array     # scalar int32 total dropped synapse events
    stim: Any              # stimulus state pytree (() for stateless stimuli)
    stats: dict            # scheme stats counters (scheme.init_stats())


def sim_step(carry: SimCarry, t, *, scheme: ExchangeScheme, state, stim,
             sim, cap, topo: Topology, probes, pad_mask=None,
             voltage_rows=None):
    """One simulation step on one partition — THE step body.

    ``scheme.exchange`` is the only place collectives may appear;
    everything else is partition-local.  ``pad_mask`` ([U] bool, True for
    real neurons) keeps padding slots inert on padded partitions;
    ``voltage_rows`` optionally remaps the probe's voltage ids onto this
    partition's local rows (see :meth:`repro.exp.ProbeSpec.collect`).
    Each layer of the body runs under its :mod:`repro.obs.scopes` name,
    so a profiler trace splits the step's device time by layer.
    """
    from repro.exp.stimulus import apply_drive, n_split
    p = sim.params
    with jax.named_scope(scopes.STIMULUS):
        keys = jax.random.split(carry.key, n_split(stim))
    with jax.named_scope(scopes.RING):
        delayed = carry.ring[carry.ptr]

    # Optional step-aware exchange (the fault-injection wrapper needs the
    # step index to corrupt/drop payloads at configured steps); ordinary
    # schemes keep the t-free protocol method.
    ex_at = getattr(scheme, "exchange_at", None)
    with jax.named_scope(scopes.EXCHANGE):
        payload = (scheme.exchange(state, delayed, cap, topo) if ex_at is None
                   else ex_at(state, delayed, cap, topo, t))
    with jax.named_scope(scopes.STIMULUS):
        sstate, drive = stim.step(carry.stim, keys[1:], t, topo.part_size, p)
    if _scheme_fuses_lif(scheme, sim):
        # fused fast path: the engine already integrated (delivery + LIF
        # in one kernel) — running apply_drive here would double-integrate
        with jax.named_scope(scopes.DELIVERY):
            lif, spikes, drop, stats = scheme.deliver_fused(
                state, payload, delayed, carry.lif, drive, sim, cap, topo)
    else:
        with jax.named_scope(scopes.DELIVERY):
            g_units, drop, stats = scheme.deliver(state, payload, delayed,
                                                  sim, cap, topo)
        with jax.named_scope(scopes.LIF):
            lif, spikes = apply_drive(carry.lif, g_units, drive, p,
                                      sim.fixed_point)
    if pad_mask is not None:
        with jax.named_scope(scopes.LIF):
            spikes = jnp.logical_and(spikes, pad_mask)

    with jax.named_scope(scopes.RING):
        ring = carry.ring.at[carry.ptr].set(spikes)
        ptr = (carry.ptr + 1) % p.delay_steps
        # health sentinels (repro.core.health) accumulate next to the
        # scheme counters; both dicts are keyed disjointly and the carry's
        # stats structure is the static union fixed at init time
        stats = {**stats, **health_step_stats(lif, sim)}
        new = SimCarry(
            lif=lif, ring=ring, ptr=ptr, key=keys[0],
            counts=carry.counts + spikes.astype(jnp.int32),
            dropped=carry.dropped + drop.astype(jnp.int32),
            stim=sstate,
            stats={k: carry.stats[k] + stats[k] for k in carry.stats})
    with jax.named_scope(scopes.PROBES):
        records = probes.collect(spikes=spikes, lif=lif, drop=drop, params=p,
                                 voltage_rows=voltage_rows)
    return new, records


def scan_steps(scheme: ExchangeScheme, state, carry: SimCarry, stim, sim,
               cap, topo: Topology, probes, t_steps: int, *, t0=None,
               pad_mask=None, voltage_rows=None):
    """Scan ``t_steps`` of :func:`sim_step` — the shared inner loop of every
    entry point (single-run, vmapped trials, emulated and shard_map
    distributed).

    ``t0`` offsets the step indices (a *traced* scalar, so a chunked run
    reuses one compiled K-step program for every chunk — the supervision
    substrate of :mod:`repro.core.health`); the default None keeps the
    historical 0-based program byte-identical."""
    def step(c, t):
        return sim_step(c, t, scheme=scheme, state=state, stim=stim, sim=sim,
                        cap=cap, topo=topo, probes=probes, pad_mask=pad_mask,
                        voltage_rows=voltage_rows)
    ts = jnp.arange(t_steps, dtype=jnp.int32)
    if t0 is not None:
        ts = ts + jnp.asarray(t0, jnp.int32)
    return jax.lax.scan(step, carry, ts)


__all__ = ["SimCarry", "scan_steps", "sim_step"]
