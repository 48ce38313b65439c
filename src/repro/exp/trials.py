"""Vmapped trial batches: the paper's 10-trial statistic in one compiled call.

The paper validates implementations by comparing per-neuron spike rates
averaged over 10 trials (Figs 6, 12, 14-15).  :func:`run_trials` vmaps the
simulation scan over a batch of seeds — one trace, one device dispatch —
and is bit-identical to a Python loop of :func:`repro.core.simulate` calls
over the same seeds.  :func:`run_dist_trials` is the same batching on the
partitioned path (the unified step core makes it the same scan): the
trial axis is vmapped *inside* each partition, so one emulated or
shard_map dispatch covers the whole seed batch.  ``mean_rates_hz`` feeds
:func:`repro.core.validate.parity` directly.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.connectome import Connectome
from repro.core.engine import (SimConfig, _init_carry, _resolve_probes,
                               _resolve_stimulus, _run_scan_trials,
                               build_synapses)
from repro.core.health import run_chunked
from repro.core.neuron import LIFState


def _seed_tuple(seeds) -> tuple:
    if isinstance(seeds, (int, np.integer)):
        seeds = tuple(range(int(seeds)))
    else:
        seeds = tuple(int(s) for s in seeds)
    if not seeds:
        raise ValueError("need at least one seed")
    return seeds


def trial_carry(n: int, cfg: SimConfig, stimulus, seeds):
    """Trial-batched scan carry: the single-run carry broadcast over a
    leading seed axis, with one PRNG stream per trial — exactly what
    ``simulate(..., seed=s)`` initializes, stacked.  Returns
    ``(carry, seeds)`` with ``seeds`` normalized to a tuple.  Shared by
    :func:`run_trials` and the serving layer's request batching
    (:mod:`repro.serving.sim`), which packs independent requests into
    the same vmapped scan."""
    seeds = _seed_tuple(seeds)
    tmpl = _init_carry(n, cfg, stimulus, 0)
    keys = jnp.stack([jax.random.PRNGKey(s) for s in seeds])
    carry = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (len(seeds),) + x.shape).copy(), tmpl)
    return carry._replace(key=keys), seeds


class TrialResult(NamedTuple):
    counts: jax.Array      # [B, n] per-trial spike counts
    dropped: jax.Array     # [B]
    state: LIFState        # leaves [B, n]
    records: dict          # probe records, each [B, T, ...]
    seeds: tuple           # the seeds, in batch order
    stats: dict = {}       # engine + health counters, each [B]

    def rates_hz(self, t_steps: int, dt_ms: float) -> np.ndarray:
        """[B, n] per-trial per-neuron rates."""
        return np.asarray(self.counts, np.float64) / (t_steps * dt_ms * 1e-3)

    def mean_rates_hz(self, t_steps: int, dt_ms: float) -> np.ndarray:
        """[n] trial-averaged rates — the parity-plot statistic."""
        return self.rates_hz(t_steps, dt_ms).mean(axis=0)


def run_trials(
    c: Connectome,
    cfg: SimConfig,
    t_steps: int,
    sugar_neurons: np.ndarray | None = None,
    seeds: int | Sequence[int] = 10,
    syn: Any | None = None,
    stimulus: Any | None = None,
    probes: Any | None = None,
    chunk_steps: int | None = None,
) -> TrialResult:
    """Run one trial per seed as a single vmapped, jitted scan.

    ``seeds`` is either a trial count B (seeds 0..B-1) or an explicit
    sequence.  Synaptic state and the stimulus are shared (broadcast)
    across trials; each trial gets its own PRNG stream, exactly as
    ``simulate(..., seed=s)`` would.

    ``chunk_steps=K`` supervises the batch the same way ``simulate()``
    does (:func:`repro.core.health.run_chunked`): ceil(T/K) reuses of one
    compiled K-step program, bit-identical to the monolithic scan, with
    ``cfg.health`` thresholds checked at chunk boundaries against the
    counters summed over the whole batch (per-lane attribution is the
    serving layer's job — :mod:`repro.serving.sim`).
    """
    with obs.span("run_trials", engine=cfg.engine):
        n = c.n
        if syn is None:
            syn = build_synapses(c, cfg)
        stimulus = _resolve_stimulus(cfg, n, sugar_neurons, stimulus)
        probes = _resolve_probes(cfg, probes)
        with obs.span("init_carry"):
            carry, seeds = trial_carry(n, cfg, stimulus, seeds)

        if chunk_steps:
            def run_chunk(cy, s, k):
                with obs.span("dispatch"):
                    return _run_scan_trials(syn, cy, stimulus, cfg, probes,
                                            k, n, jnp.int32(s))
            # records are [B, T, ...] on the batched path -> time axis 1;
            # the rate envelope normalizes by the batch-summed neuron count
            carry, records = run_chunked(
                run_chunk, carry, t_steps, chunk_steps, time_axis=1,
                health=cfg.health, n=n * len(seeds), dt_ms=cfg.params.dt)
        else:
            with obs.span("dispatch"):
                carry, records = _run_scan_trials(syn, carry, stimulus, cfg,
                                                  probes, t_steps, n)
    return TrialResult(counts=carry.counts, dropped=carry.dropped,
                       state=carry.lif, records=records, seeds=seeds,
                       stats=dict(carry.stats))


class DistTrialResult(NamedTuple):
    """Trial-batched distributed run; per-neuron data in original ids."""
    counts: np.ndarray     # [B, n_orig] per-trial spike counts
    dropped: np.ndarray    # [B]
    state: Any             # LIFState, leaves [B, n_orig]
    records: dict          # probe records, each [B, T, ...] (original ids)
    stats: dict            # scheme counters, each [B]
    seeds: tuple

    def rates_hz(self, t_steps: int, dt_ms: float) -> np.ndarray:
        """[B, n] per-trial per-neuron rates."""
        return np.asarray(self.counts, np.float64) / (t_steps * dt_ms * 1e-3)

    def mean_rates_hz(self, t_steps: int, dt_ms: float) -> np.ndarray:
        """[n] trial-averaged rates — the parity-plot statistic."""
        return self.rates_hz(t_steps, dt_ms).mean(axis=0)


def run_dist_trials(
    d,
    cfg,
    t_steps: int,
    sugar_neurons: np.ndarray | None = None,
    seeds: int | Sequence[int] = 10,
    stimulus: Any | None = None,
    probes: Any | None = None,
    mesh=None,
    emulate: bool = False,
) -> DistTrialResult:
    """Distributed counterpart of :func:`run_trials`: one partitioned
    dispatch (vmap emulation or shard_map) covering the whole seed batch,
    bit-identical to a Python loop of
    :func:`repro.core.distributed.simulate_distributed` over the same
    seeds.  ``d`` is a :class:`repro.core.dcsr.DCSR`, ``cfg`` a
    :class:`repro.core.distributed.DistConfig`."""
    from repro.core.distributed import _assemble, _run_partitioned
    seeds = _seed_tuple(seeds)
    # keys[p, b] = what simulate_distributed(seed=seeds[b]) hands part p
    keys = jnp.stack(
        [jax.random.split(jax.random.PRNGKey(s), d.n_parts) for s in seeds],
        axis=1)                                          # [P, B, 2]
    out, records, probes, owner = _run_partitioned(
        d, cfg, t_steps, keys, sugar_neurons, stimulus, probes, mesh,
        emulate, trials=True)
    counts, dropped, state, recs, stats = _assemble(d, out, records, probes,
                                                    owner)
    return DistTrialResult(counts=counts, dropped=np.asarray(dropped),
                           state=state, records=recs, stats=stats,
                           seeds=seeds)


__all__ = ["DistTrialResult", "TrialResult", "run_dist_trials", "run_trials",
           "trial_carry"]
