"""Distributed multi-core SNN simulation via shard_map (paper §3.2.2-3.2.3).

Maps DCSR partitions onto a device mesh axis ("cores"), one partition per
device.  The per-partition step is the SAME function the monolithic
``simulate()`` runs — the unified step core in :mod:`repro.core.step` —
parameterized by a registered exchange scheme
(:mod:`repro.core.exchange`): ``bitmap`` (all_gather of the spike bitmap,
fixed comm volume), ``event`` (all_gather of K-slot compacted active-id
lists, comm ∝ activity), or ``blocked`` (event exchange across the cut +
tile-granular Pallas delivery inside each partition).  Every partition is
computationally self-contained except for ``scheme.exchange`` — exactly
the paper's framing of the edge cut as a sparse, data-dependent halo.

Because the step body is shared, the distributed path has full
observability parity with the monolithic one: :class:`repro.exp.ProbeSpec`
records (raster / voltage / pop-rate / drops) are collected in-scan per
partition and mapped back to original neuron ids through ``inv_perm``
(pad neurons never appear in any record or count), and
:func:`repro.exp.run_dist_trials` vmaps the whole partitioned scan over a
seed batch.

Stimulation flows through the same :mod:`repro.exp` stimulus pytrees as
the monolithic loop via :func:`repro.exp.shard_stimulus` (stateless
stimuli only).

The same step also runs unsharded under vmap (``emulate=True``) so
semantics are testable on one device; the shard_map path is exercised in
tests via a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count.
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
from jax.sharding import Mesh, PartitionSpec as P

from repro import obs

from .capacity import DISTRIBUTED_CAPACITY, CapacityConfig, merge_legacy_capacity
from .dcsr import DCSR
from .engine import SimConfig
from .exchange import (DistArrays, Topology, available_schemes,
                       build_dist_arrays, get_scheme)
from .health import (SimCheckpointer, carry_counters, health_stats_init,
                     run_chunked)
from .neuron import LIFState, init_state
from .step import SimCarry, scan_steps

AXIS = "cores"


@dataclasses.dataclass(frozen=True)
class DistConfig:
    sim: SimConfig
    scheme: str = "event"        # see repro.core.exchange / docs/distributed.md
    # Deprecated capacity shims -> capacity (CapacityConfig); explicit
    # writes warn and merge into .capacity, which is the one read path.
    spike_capacity: Optional[int] = None
    syn_budget: Optional[int] = None
    block_capacity: Optional[int] = None
    capacity: Optional[CapacityConfig] = None

    def __post_init__(self):
        cap = merge_legacy_capacity(
            self.capacity, self.spike_capacity, self.syn_budget,
            self.block_capacity, DISTRIBUTED_CAPACITY, "DistConfig")
        object.__setattr__(self, "capacity", cap)
        # consume the shims: dataclasses.replace must never re-apply them
        for f in ("spike_capacity", "syn_budget", "block_capacity"):
            object.__setattr__(self, f, None)


class DistResult(NamedTuple):
    """``SimResult``-shaped distributed result: everything per-neuron is
    mapped back to *original* neuron ids through ``inv_perm``."""
    counts: np.ndarray        # [n_orig] spike counts
    dropped: int
    state: Any                # LIFState, leaves [n_orig]
    raster: np.ndarray | None  # [T, n_orig] (iff the raster probe is on)
    records: dict             # ProbeSpec records, leading axis T
    stats: dict               # scheme counters (e.g. blocked tiles_live)


def make_core_mesh(n_cores: int, devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    if len(devices) < n_cores:
        raise ValueError(f"need {n_cores} devices, have {len(devices)}")
    return Mesh(np.array(devices[:n_cores]), (AXIS,))


# --------------------------------------------------------------------------
# Partitioned run plumbing (shared by the single-seed and trial-batch paths)
# --------------------------------------------------------------------------

def _resolve_dist_stimulus(d: DCSR, sc: SimConfig, sugar_neurons, stimulus):
    from repro.exp.stimulus import legacy_stimulus, shard_stimulus
    if stimulus is None:
        if sugar_neurons is not None:
            warnings.warn(
                "sugar_neurons= is deprecated; pass stimulus= instead "
                "(e.g. repro.exp.PoissonDrive(mask=...) or "
                "legacy_stimulus(cfg, n, sugar_idx, masked=True))",
                DeprecationWarning, stacklevel=4)
        stimulus = legacy_stimulus(sc, d.n_orig, sugar_idx=sugar_neurons,
                                   masked=True)
    elif sugar_neurons is not None:
        raise ValueError(
            "pass either sugar_neurons (legacy drive) or stimulus, "
            "not both — an explicit stimulus ignores sugar_neurons")
    return shard_stimulus(stimulus, d)


def _resolve_dist_probes(d: DCSR, sc: SimConfig, probes):
    """Resolve the probe spec and precompute the per-partition voltage-row
    remap: ``rows[p, i]`` is probe id i's local row on partition p (0 when
    not owned — the host keeps only the owning partition's trace)."""
    if probes is None:
        from repro.exp.probes import ProbeSpec
        probes = ProbeSpec(raster=sc.collect_raster)
    P_, U = d.n_parts, d.part_size
    ids = np.asarray(probes.voltage, dtype=np.int64)
    bad = ids[(ids < 0) | (ids >= d.n_orig)]
    if bad.size:
        raise ValueError(f"voltage probe ids {bad.tolist()} out of range "
                         f"for n={d.n_orig}")
    gid = d.perm[ids] if ids.size else ids
    owner, local = gid // U, gid % U
    rows = np.where(owner[None, :] == np.arange(P_)[:, None], local[None, :],
                    0).astype(np.int32)                     # [P, n_probe]
    return probes, jnp.asarray(rows), owner.astype(np.int64)


def _init_dist_carry(d: DCSR, cfg: DistConfig, stim, scheme,
                     keys: np.ndarray) -> SimCarry:
    """Stacked per-partition carry; ``keys`` is [P, 2] (single run) or
    [P, B, 2] (trial batch — every extra leading key axis becomes a batch
    axis on all per-partition leaves)."""
    P_, U = d.n_parts, d.part_size
    sc = cfg.sim
    batch = keys.shape[1:-1]            # () or (B,)
    shp = (P_,) + batch

    def bcast(x, tail):
        return jnp.broadcast_to(x, shp + tail).copy()

    lif0 = init_state(P_ * U, sc.params, sc.fixed_point)
    lif0 = jax.tree.map(
        lambda x: bcast(x.reshape((P_,) + (1,) * len(batch) + (U,))
                        if batch else x.reshape(P_, U), (U,)), lif0)
    stats0 = {k: bcast(v, ())
              for k, v in {**scheme.init_stats(),
                           **health_stats_init(sc)}.items()}
    return SimCarry(
        lif=lif0,
        ring=jnp.zeros(shp + (sc.params.delay_steps, U), dtype=bool),
        ptr=jnp.zeros(shp, jnp.int32),
        key=jnp.asarray(keys),
        counts=jnp.zeros(shp + (U,), jnp.int32),
        dropped=jnp.zeros(shp, jnp.int32),
        stim=stim.init_state(U),
        stats=stats0,
    )


def _partition_run(scheme, cfg: DistConfig, probes, t_steps: int,
                   topo: Topology, trials: bool):
    """The per-partition run: the unified scan, optionally vmapped over a
    leading trial axis of the carry (state/stimulus broadcast).  ``t0``
    is the *traced* step offset (chunked supervision reuses one compiled
    K-step program per chunk — see :mod:`repro.core.health`)."""
    def run_one(carry, state, stim, pad, vrows, t0):
        def go(cy):
            return scan_steps(scheme, state, cy, stim, cfg.sim, cfg.capacity,
                              topo, probes, t_steps, t0=t0, pad_mask=pad,
                              voltage_rows=vrows)
        return jax.vmap(go)(carry) if trials else go(carry)
    return run_one


@functools.partial(jax.jit, static_argnums=(0, 6, 7, 8, 9),
                   donate_argnums=(1,))
def _run_emulated_jit(scheme_name: str, carry, state, stim, pad, vrows,
                      cfg: DistConfig, probes, t_steps: int, trials: bool,
                      t0=None):
    """vmap over the partition dim with a named axis -> collectives work
    on one device (semantics-identical to the shard_map execution)."""
    P_, U = pad.shape
    run_one = _partition_run(get_scheme(scheme_name), cfg, probes, t_steps,
                             Topology(P_, U, axis=AXIS), trials)
    return jax.vmap(run_one, in_axes=(0, 0, 0, 0, 0, None),
                    axis_name=AXIS)(carry, state, stim, pad, vrows, t0)


# Compile-cache instrumentation (repro.obs): per-signature hit/miss
# counters and trace/compile wall with a telemetry session active; the
# plain jit call otherwise.
_run_emulated = obs.InstrumentedJit(_run_emulated_jit,
                                    "distributed.run_emulated",
                                    static_argnums=(0, 6, 7, 8, 9))


@functools.lru_cache(maxsize=64)
def _shard_map_fn(scheme_name: str, cfg: DistConfig, probes, t_steps: int,
                  trials: bool, mesh: Mesh, P_: int, U: int):
    """One jitted shard_map program per static signature — repeat
    ``simulate_distributed(emulate=False)`` calls are cache hits, matching
    the module-level jit of the emulated path."""
    run_one = _partition_run(get_scheme(scheme_name), cfg, probes, t_steps,
                             Topology(P_, U, axis=AXIS), trials)

    def sharded(carry, state, stim, pad, vrows, t0):
        strip = lambda t: jax.tree.map(lambda x: x[0], t)   # local P dim
        out = run_one(strip(carry), strip(state), strip(stim), pad[0],
                      vrows[0], t0)
        return jax.tree.map(lambda x: x[None], out)

    return obs.InstrumentedJit(
        jax.jit(jax.shard_map(
            sharded, mesh=mesh,
            in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(AXIS), P()),
            out_specs=P(AXIS), check_vma=False)),
        f"distributed.shard_map.{scheme_name}")


def _run_shard_map(scheme_name: str, carry, state, stim, pad, vrows,
                   cfg: DistConfig, probes, t_steps: int, trials: bool,
                   mesh: Mesh, t0=None):
    P_, U = pad.shape
    fn = _shard_map_fn(scheme_name, cfg, probes, t_steps, trials, mesh,
                       P_, U)
    if t0 is None:
        t0 = jnp.int32(0)   # replicated scalar: the spec needs a leaf
    return fn(carry, state, stim, pad, vrows, t0)


def _run_partitioned(d: DCSR, cfg: DistConfig, t_steps: int, keys,
                     sugar_neurons, stimulus, probes, mesh, emulate: bool,
                     trials: bool, chunk_steps: Optional[int] = None,
                     checkpoint_dir: Optional[str] = None,
                     resume: bool = False, async_checkpoint: bool = False):
    if cfg.scheme == "local" or cfg.scheme not in available_schemes():
        raise ValueError(
            f"unknown distributed exchange scheme {cfg.scheme!r}; "
            f"available: {sorted(set(available_schemes()) - {'local'})}")
    scheme = get_scheme(cfg.scheme)
    with obs.span("build", what="scheme_state", scheme=cfg.scheme):
        state = scheme.build(d, cfg.sim, cfg.capacity)
    stim = _resolve_dist_stimulus(d, cfg.sim, sugar_neurons, stimulus)
    probes, vrows, owner = _resolve_dist_probes(d, cfg.sim, probes)
    pad = jnp.asarray(d.inv_perm.reshape(d.n_parts, d.part_size) >= 0)
    carry0 = _init_dist_carry(d, cfg, stim, scheme, keys)
    if not emulate and mesh is None:
        mesh = make_core_mesh(d.n_parts)

    def run(carry, k, t0):
        if emulate:
            return _run_emulated(cfg.scheme, carry, state, stim, pad, vrows,
                                 cfg, probes, k, trials, t0)
        return _run_shard_map(cfg.scheme, carry, state, stim, pad, vrows,
                              cfg, probes, k, trials, mesh, t0)

    # a telemetry session routes single runs through the chunk driver
    # (one chunk when chunk_steps is None) for the per-chunk event
    # stream; the trial-batched path stays unsupervised (spans and
    # compile metrics still apply)
    supervised = (chunk_steps is not None or checkpoint_dir is not None
                  or cfg.sim.health is not None
                  or (obs.active() is not None and not trials))
    if not supervised:
        out, records = run(carry0, t_steps, None)
    else:
        if trials:
            raise ValueError(
                "chunked supervision (chunk_steps / checkpoint_dir / "
                "health) is not supported on the trial-batched path; "
                "supervise seeds as separate simulate_distributed runs")
        ckpt = (SimCheckpointer(checkpoint_dir, async_save=async_checkpoint)
                if checkpoint_dir is not None else None)
        out, records = run_chunked(
            lambda cy, s, k: run(cy, k, jnp.int32(s)),
            carry0, t_steps, chunk_steps,
            time_axis=1,            # records are partition-stacked [P, K, ..]
            health=cfg.sim.health, n=d.n_orig, dt_ms=cfg.sim.params.dt,
            checkpointer=ckpt, resume=resume,
            host_hook=getattr(scheme, "host_supervise", None))
    return out, records, probes, owner


# --------------------------------------------------------------------------
# Mapping partition-stacked results back to original neuron ids
# --------------------------------------------------------------------------

def _to_orig(d: DCSR, arr, dtype=None):
    """[P, *mid, U] partition-stacked -> [*mid, n_orig] in original ids;
    pad slots are dropped (they can never contribute — by construction)."""
    arr = np.asarray(arr)
    mid = arr.shape[1:-1]
    flat = np.moveaxis(arr, 0, -2).reshape(
        mid + (d.n_parts * d.part_size,))
    out = np.zeros(mid + (d.n_orig,), dtype=dtype or arr.dtype)
    valid = d.inv_perm >= 0
    out[..., d.inv_perm[valid]] = flat[..., valid]
    return out


def _assemble_records(d: DCSR, records: dict, probes, owner, n_real: int
                      ) -> dict:
    """Per-partition probe records [P, *mid, ...] -> monolithic-shaped
    records in original neuron ids."""
    out = {}
    for name, arr in records.items():
        arr = np.asarray(arr)
        if name == "raster":
            out[name] = _to_orig(d, arr)
        elif name == "v":
            # each partition traced every probe id against its own rows
            # (the record only exists when ids were probed); keep the
            # owning partition's trace per id
            out[name] = np.stack(
                [arr[owner[i], ..., i] for i in range(arr.shape[-1])],
                axis=-1)
        elif name == "pop_rate_hz":
            # per-partition mean over U (incl. inert pads) -> global mean
            # over the n_orig real neurons
            out[name] = arr.astype(np.float64).sum(axis=0) * (
                d.part_size / n_real)
        elif name == "dropped":
            out[name] = arr.sum(axis=0)
        else:                                   # scheme-agnostic fallback
            out[name] = arr.sum(axis=0)
    return out


def _assemble(d: DCSR, out: SimCarry, records: dict, probes, owner):
    counts = _to_orig(d, out.counts, dtype=np.int64)
    state = jax.tree.map(lambda x: _to_orig(d, x), out.lif)
    recs = _assemble_records(d, records, probes, owner, d.n_orig)
    stats = {k: np.asarray(v).sum(axis=0) for k, v in out.stats.items()}
    dropped = np.asarray(out.dropped).sum(axis=0)
    return counts, dropped, state, recs, stats


# --------------------------------------------------------------------------
# Entry points
# --------------------------------------------------------------------------

def simulate_distributed(
    d: DCSR,
    cfg: DistConfig,
    t_steps: int,
    sugar_neurons: np.ndarray | None = None,
    seed: int = 0,
    mesh: Mesh | None = None,
    emulate: bool = False,
    stimulus=None,
    probes=None,
    chunk_steps: Optional[int] = None,
    checkpoint_dir: Optional[str] = None,
    resume: bool = False,
    async_checkpoint: bool = False,
) -> DistResult:
    """Run the partitioned network.  ``emulate=True`` uses vmap with an
    axis name on one device (semantics-identical); otherwise shard_map
    over a "cores" mesh axis with one partition per device.

    ``cfg.scheme`` selects a registered exchange scheme (see
    :func:`repro.core.exchange.available_schemes`).  ``stimulus`` is any
    stateless :class:`repro.exp.Stimulus` addressed in *original* neuron
    ids (sharded onto the partitioning here); ``probes`` any
    :class:`repro.exp.ProbeSpec`, with records returned in original ids
    exactly like :func:`repro.core.simulate`.  For a vmapped seed batch
    use :func:`repro.exp.run_dist_trials`.

    ``chunk_steps`` / ``checkpoint_dir`` / ``resume`` mirror
    :func:`repro.core.simulate`'s chunked supervision (bit-identical
    chunking, chunk-boundary health checks against ``cfg.sim.health``,
    checkpoint/resume) on the partitioned path; see ``docs/resilience.md``.
    With a telemetry session active (:func:`repro.obs.telemetry`) the run
    emits the same span/chunk/compile event stream as the monolithic
    path and surfaces the compile cache on
    ``DistResult.stats["compile_cache"]``; see ``docs/observability.md``.
    """
    tele = obs.active()
    with obs.span("simulate_distributed", scheme=cfg.scheme):
        if tele is not None:
            tele.emit("run_start", kind="simulate_distributed",
                      scheme=cfg.scheme, n=d.n_orig, t_steps=t_steps,
                      chunk_steps=chunk_steps,
                      fixed_point=cfg.sim.fixed_point)
        t_run = time.monotonic()
        keys = jax.random.split(jax.random.PRNGKey(seed), d.n_parts)
        out, records, probes, owner = _run_partitioned(
            d, cfg, t_steps, keys, sugar_neurons, stimulus, probes, mesh,
            emulate, trials=False, chunk_steps=chunk_steps,
            checkpoint_dir=checkpoint_dir, resume=resume,
            async_checkpoint=async_checkpoint)
        counts, dropped, state, recs, stats = _assemble(d, out, records,
                                                        probes, owner)
        if tele is not None:
            tele.emit("run_end", steps=t_steps,
                      wall_s=round(time.monotonic() - t_run, 6),
                      counters=carry_counters(out),
                      metrics=tele.metrics.counters())
            stats["compile_cache"] = tele.metrics.compile_snapshot()
    return DistResult(counts=counts, dropped=int(dropped), state=state,
                      raster=recs.get("raster"), records=recs, stats=stats)


__all__ = ["AXIS", "DistArrays", "DistConfig", "DistResult",
           "build_dist_arrays", "make_core_mesh", "simulate_distributed"]
