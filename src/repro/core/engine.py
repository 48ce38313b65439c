"""Connectome LIF simulation loop over pluggable delivery engines.

Synaptic-delivery strategies live in :mod:`repro.core.engines` (one module
per strategy, registered by name); stimulation and observability live in
:mod:`repro.exp` (stimulus protocols, in-scan probes).  This module owns
everything that is engine- and stimulus-independent: the LIF state machine
(float or fixed-point), the ring-buffer implementation of the uniform
1.8 ms synaptic delay, and the scan over timesteps.

The whole run is a single jitted computation per (engine, stimulus, config,
probes, t_steps) signature: device synaptic state is built once per
:func:`simulate` call, the carry (ring buffer + LIF state + counters +
stimulus state) is donated so XLA updates it in place across calls, and
repeated calls with the same static signature skip retracing entirely — the
property the benchmark harness relies on.  :func:`repro.exp.run_trials`
vmaps the same scan over a seed batch for the paper's trial-averaged
statistics.
"""

from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from .capacity import MONOLITHIC_CAPACITY, CapacityConfig, merge_legacy_capacity
from .connectome import Connectome
from .engines import available_engines, engine_init_stats, get_engine
from .health import (HealthConfig, SimCheckpointer, carry_counters,
                     health_stats_init, run_chunked)
from .neuron import LIFParams, LIFState, init_state
from .step import SimCarry, scan_steps


@dataclasses.dataclass(frozen=True)
class SimConfig:
    params: LIFParams = LIFParams()
    engine: str = "csr"             # see repro.core.engines / docs/engines.md
    fixed_point: bool = False
    quantize_bits: Optional[int] = None   # 9 = Loihi; None = raw weights
    # Legacy stimulus fields: consumed by repro.exp.stimulus.legacy_stimulus
    # when simulate() is called without an explicit stimulus.
    poisson_to_v: bool = True       # True = Brian2 semantics; False = Loihi approx
    poisson_rate_hz: float = 150.0
    poisson_weight: float = 180.0   # weight units delivered per Poisson event
    background_rate_hz: float = 0.0  # scaling-study probabilistic spiking
    # Deprecated capacity shims -> capacity (CapacityConfig); explicit
    # writes warn and merge into .capacity, which is the one read path.
    spike_capacity: Optional[int] = None
    syn_budget: Optional[int] = None
    block_capacity: Optional[int] = None
    ell_width_cap: int = 4096        # SSD fan-in cap
    collect_raster: bool = False     # deprecated: use ProbeSpec(raster=True)
    capacity: Optional[CapacityConfig] = None   # event-path static budgets
    health: Optional[HealthConfig] = None   # in-scan sentinels + thresholds

    def __post_init__(self):
        cap = merge_legacy_capacity(
            self.capacity, self.spike_capacity, self.syn_budget,
            self.block_capacity, MONOLITHIC_CAPACITY, "SimConfig")
        object.__setattr__(self, "capacity", cap)
        # consume the shims: dataclasses.replace must never re-apply them
        for f in ("spike_capacity", "syn_budget", "block_capacity"):
            object.__setattr__(self, f, None)
        if self.collect_raster:
            warnings.warn(
                "SimConfig(collect_raster=True) is deprecated; pass "
                "probes=ProbeSpec(raster=True) instead",
                DeprecationWarning, stacklevel=3)


def build_synapses(c: Connectome, cfg: SimConfig) -> Any:
    """Build the device-resident synaptic state for ``cfg.engine``.

    Returns the engine-specific state pytree; pass it back to
    :func:`simulate` via ``syn=`` to amortize the host-side build across
    repeated runs (benchmark pattern)."""
    with obs.span("build", what="synapses", engine=cfg.engine):
        return get_engine(cfg.engine).build(c, cfg)


# --------------------------------------------------------------------------
# Full simulation loop
# --------------------------------------------------------------------------

class SimResult(NamedTuple):
    counts: jax.Array
    state: LIFState
    dropped: jax.Array
    raster: jax.Array | None
    records: dict          # ProbeSpec-selected [T, ...] arrays
    stats: dict = {}       # scheme + health counters (repro.core.health)


def _scan_steps(syn, carry: SimCarry, stim, cfg: SimConfig, probes,
                t_steps: int, n: int, t0=None):
    """Scan `t_steps` steps of the ONE step body (:mod:`repro.core.step`)
    through the degenerate P=1 ``local`` exchange scheme; shared by the
    single-run and vmapped-trials entry points.

    ``syn`` is the engine state pytree and ``stim`` the stimulus pytree
    (their static fields key the jit cache); all stimulus-specific work —
    Poisson drive, background spiking, clocked currents — flows through
    ``stim.step``, all observability through ``probes.collect``.  ``t0``
    is a *traced* step offset: a chunked run reuses one compiled K-step
    program for every chunk.
    """
    from .exchange import Topology, get_scheme
    return scan_steps(get_scheme("local"), syn, carry, stim, cfg,
                      cfg.capacity, Topology(1, n, axis=None), probes,
                      t_steps, t0=t0)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6), donate_argnums=(1,))
def _run_scan_jit(syn, carry: SimCarry, stim, cfg: SimConfig, probes,
                  t_steps: int, n: int, t0=None):
    return _scan_steps(syn, carry, stim, cfg, probes, t_steps, n, t0)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6), donate_argnums=(1,))
def _run_scan_trials_jit(syn, carry: SimCarry, stim, cfg: SimConfig, probes,
                         t_steps: int, n: int, t0=None):
    """Batched trials: vmap the scan over a leading seed/trial axis of the
    carry; syn and stim are broadcast (shared across trials)."""
    return jax.vmap(
        lambda cy: _scan_steps(syn, cy, stim, cfg, probes, t_steps, n, t0)
    )(carry)


# Compile-cache instrumentation (repro.obs): with a telemetry session
# active, calls are keyed per signature with hit/miss counters and
# per-signature trace/compile wall + cost_analysis — the ROADMAP's
# "surface hit rates".  Without a session these are the plain jit calls.
_run_scan = obs.InstrumentedJit(_run_scan_jit, "engine.run_scan",
                                static_argnums=(3, 4, 5, 6))
_run_scan_trials = obs.InstrumentedJit(_run_scan_trials_jit,
                                       "engine.run_trials",
                                       static_argnums=(3, 4, 5, 6))


def _init_carry(n: int, cfg: SimConfig, stimulus, seed: int) -> SimCarry:
    return SimCarry(
        lif=init_state(n, cfg.params, cfg.fixed_point),
        ring=jnp.zeros((cfg.params.delay_steps, n), dtype=bool),
        ptr=jnp.int32(0),
        key=jax.random.PRNGKey(seed),
        counts=jnp.zeros(n, jnp.int32),
        dropped=jnp.int32(0),
        stim=stimulus.init_state(n),
        stats={**engine_init_stats(cfg.engine), **health_stats_init(cfg)},
    )


def _resolve_stimulus(cfg: SimConfig, n: int, sugar_neurons, stimulus):
    if stimulus is not None:
        if sugar_neurons is not None:
            raise ValueError(
                "pass either sugar_neurons (legacy drive) or stimulus, "
                "not both — an explicit stimulus ignores sugar_neurons")
        return stimulus
    from repro.exp.stimulus import legacy_stimulus
    sugar_idx = None
    if sugar_neurons is not None:
        warnings.warn(
            "sugar_neurons= is deprecated; pass stimulus= instead (e.g. "
            "repro.exp.PoissonDrive(idx=...) or legacy_stimulus(cfg, n, "
            "sugar_idx))", DeprecationWarning, stacklevel=3)
        sugar_idx = np.asarray(sugar_neurons).astype(np.int32)
    return legacy_stimulus(cfg, n, sugar_idx)


def _resolve_probes(cfg: SimConfig, probes):
    if probes is not None:
        return probes
    from repro.exp.probes import ProbeSpec
    return ProbeSpec(raster=cfg.collect_raster)


def simulate(
    c: Connectome,
    cfg: SimConfig,
    t_steps: int,
    sugar_neurons: np.ndarray | None = None,
    seed: int = 0,
    syn: Any | None = None,
    stimulus: Any | None = None,
    probes: Any | None = None,
    chunk_steps: Optional[int] = None,
    checkpoint_dir: Optional[str] = None,
    resume: bool = False,
    async_checkpoint: bool = False,
) -> SimResult:
    """Run `t_steps` of the network; returns per-neuron spike counts (the
    paper's validation statistic) plus any probe records.

    ``cfg.engine`` selects a registered delivery engine (see
    :func:`repro.core.engines.available_engines`); ``syn`` optionally
    supplies a prebuilt state from :func:`build_synapses`.  ``stimulus``
    is any :class:`repro.exp.Stimulus` (default: the legacy sugar-Poisson
    + background drive reconstructed from ``cfg`` and ``sugar_neurons``);
    ``probes`` is a :class:`repro.exp.ProbeSpec` (default: raster iff
    ``cfg.collect_raster``).

    ``chunk_steps=K`` runs the same simulation as ceil(T/K) reuses of one
    compiled K-step program with the carry threaded host-side — the
    result is bit-identical to the monolithic scan, but the host gets a
    supervision point every K steps where ``cfg.health`` thresholds are
    checked and (with ``checkpoint_dir``) the carry is checkpointed, so a
    killed run restarted with ``resume=True`` reproduces the
    uninterrupted run bit-for-bit.  See :mod:`repro.core.health` and
    ``docs/resilience.md``.

    With a telemetry session active (:func:`repro.obs.telemetry`), the
    run emits phase spans, per-chunk JSONL events, and compile-cache
    metrics (surfaced on ``SimResult.stats["compile_cache"]``) — all
    host-side, results bit-identical to an uninstrumented run; see
    ``docs/observability.md``.
    """
    tele = obs.active()
    with obs.span("simulate", engine=cfg.engine):
        n = c.n
        if syn is None:
            syn = build_synapses(c, cfg)
        stimulus = _resolve_stimulus(cfg, n, sugar_neurons, stimulus)
        probes = _resolve_probes(cfg, probes)
        with obs.span("init_carry"):
            carry = _init_carry(n, cfg, stimulus, seed)
        if tele is not None:
            tele.emit("run_start", kind="simulate", engine=cfg.engine,
                      n=n, t_steps=t_steps, chunk_steps=chunk_steps,
                      fixed_point=cfg.fixed_point)
        t_run = time.monotonic()
        single = (chunk_steps is None and checkpoint_dir is None
                  and cfg.health is None)
        # telemetry routes through the supervised chunk driver (one chunk
        # when chunk_steps is None) so the per-chunk event stream exists;
        # the chunked scan is bit-identical to the monolithic one
        if single and tele is None:
            with obs.span("dispatch"):
                carry, records = _run_scan(syn, carry, stimulus, cfg, probes,
                                           t_steps, n)
        else:
            ckpt = (SimCheckpointer(checkpoint_dir,
                                    async_save=async_checkpoint)
                    if checkpoint_dir is not None else None)

            def run_chunk(cy, s, k):
                # the one chunk of an unsupervised run is the plain call's
                # program (no step offset), so telemetry observes the
                # program that runs without it
                t0 = () if single else (jnp.int32(s),)
                with obs.span("dispatch"):
                    return _run_scan(syn, cy, stimulus, cfg, probes, k, n,
                                     *t0)

            carry, records = run_chunked(
                run_chunk, carry, t_steps, chunk_steps, time_axis=0,
                health=cfg.health, n=n, dt_ms=cfg.params.dt,
                checkpointer=ckpt, resume=resume)
        stats = dict(carry.stats)
        if tele is not None:
            jax.block_until_ready(carry)
            tele.emit("run_end", steps=t_steps,
                      wall_s=round(time.monotonic() - t_run, 6),
                      counters=carry_counters(carry),
                      metrics=tele.metrics.counters())
            stats["compile_cache"] = tele.metrics.compile_snapshot()
    return SimResult(counts=carry.counts, state=carry.lif,
                     dropped=carry.dropped, raster=records.get("raster"),
                     records=records, stats=stats)


def spike_rates_hz(counts: jax.Array, t_steps: int, dt_ms: float) -> jax.Array:
    return counts.astype(jnp.float32) / (t_steps * dt_ms * 1e-3)


__all__ = ["SimConfig", "SimCarry", "SimResult", "available_engines",
           "build_synapses", "simulate", "spike_rates_hz"]
