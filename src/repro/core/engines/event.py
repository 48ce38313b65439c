"""Event engine: active-set event-driven delivery (the Loihi-like path).

Compacts spiking neurons into a fixed-capacity index list via the
block-hierarchical compaction in :mod:`repro.core.compaction`, ragged-gathers
their fan-out synapse ranges into a bounded synapse budget, and scatter-adds
into targets.  Cost ∝ activity — the paper's "performance advantages
increase with sparser activity" path.

Per-step cost is O(n/B + B_cap·B + ⌈min(events, S_cap)/C⌉·C) where B =
128 (the compaction block), B_cap = ``block_capacity``, S_cap =
``syn_budget`` and C = :func:`repro.core.compaction.slot_chunk` — the
slot loop visits only the chunks of the budget that this step's events
fill (counted in the ``slot_chunks`` stat).  The only O(n) work left is
vectorized elementwise (the block any-reduce and the drop-accounting
fan-out dot), not the O(n) compaction scan the flat
``jnp.where(spikes, size=K)`` used to pay regardless of activity.

Capacity overruns are *counted* (``dropped``), never silent — including
spikes beyond ``spike_capacity``/``block_capacity``, whose whole fan-out is
reported as dropped synapses (exact: requested − delivered, with the
requested total computed from the full spike vector).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import scopes

from ..capacity import CapacityConfig
from ..compaction import (BLOCK, active_fanout_total, deliver_ragged,
                          derived_block_capacity, n_blocks, slot_owner,
                          two_level_active)
from ..compress import quantize_weights
from ..connectome import Connectome
from .base import register, register_state, static_field

__all__ = ["Capacity", "EventEngine", "EventState", "auto_capacity",
           "slot_owner"]   # slot_owner re-exported from core.compaction


@register_state
@dataclasses.dataclass(frozen=True)
class EventState:
    out_indptr: jax.Array             # [n+1] i32 fan-out row pointers
    out_tgt: jax.Array                # [nnz] i32
    out_w: jax.Array                  # [nnz] f32
    n: int = static_field(default=0)


#: Joint static-shape provisioning now lives in
#: :class:`repro.core.capacity.CapacityConfig`; ``Capacity`` remains as the
#: historical alias (``auto_capacity`` returns it, ``as_config_kwargs``
#: routes through the ``capacity=`` config field).
Capacity = CapacityConfig


def auto_capacity(c: Connectome, rate_hz: float, dt_ms: float = 0.1,
                  margin: float = 4.0, fanout: str = "p99.9",
                  block: int = BLOCK) -> CapacityConfig:
    """Provision the event path's static budgets for an expected activity
    level — the static-shape analogue of Loihi's 'work ~ actual spike
    count'.  The engine still *counts* drops, so under-provisioning is
    observable.

    The three budgets are derived jointly from one provisioned spike level
    ``Kp = margin × expected spikes/step``:

    * ``spike_capacity`` = ``Kp`` floored at 64 (quiet networks keep burst
      headroom — the slot list is cheap);
    * ``block_capacity`` = ``Kp`` 128-blocks (spikes can never occupy more
      blocks than their count), floored at 32 and capped at the total
      block count — this bounds the within-block compaction scan;
    * ``syn_budget`` = ``Kp`` mean fan-outs + a ``margin``-scaled
      Poisson-fluctuation term (√Kp·std) + a hub cushion for heavy-tailed
      fan-out.  ``fanout`` picks the cushion: a percentile string
      (``"p99"``, ``"p99.9"``, ...), ``"max"`` (never drop on a single
      hub), or ``"mean"`` for the legacy ``cap·mean·margin`` formula,
      which both under-provisions simultaneous hub spikes *and*
      over-provisions the common case by ~margin² (the margin already in
      ``spike_capacity`` gets multiplied in again).

    ``block_capacity`` prices the per-step O(B_cap·128) compaction and
    ``syn_budget`` caps the slot work, which otherwise follows the events
    delivered; drops stay exactly counted, so any residual
    under-provisioning is observable rather than silent.
    """
    exp_spikes = max(1.0, c.n * rate_hz * dt_ms * 1e-3)
    kp = margin * exp_spikes
    cap = int(max(64, min(c.n, kp)))
    fo = np.diff(c.out_indptr)
    if fanout == "mean":
        mean_fo = max(1.0, c.nnz / c.n)
        budget = int(max(4096, cap * mean_fo * margin))
    else:
        if fanout == "max":
            hub = float(fo.max()) if c.nnz else 0.0
        elif fanout.startswith("p"):
            hub = float(np.percentile(fo, float(fanout[1:]))) if c.nnz else 0.0
        else:
            raise ValueError(
                f"unknown fanout statistic {fanout!r} "
                "(want 'mean', 'max', or a percentile like 'p99.9')")
        budget = int(max(4096, kp * fo.mean()
                         + margin * np.sqrt(kp) * fo.std() + hub))
    budget = min(budget, max(4096, int(c.nnz)))
    bcap = max(1, min(n_blocks(c.n, block), max(32, int(np.ceil(kp)))))
    return CapacityConfig(spike_capacity=cap, syn_budget=budget,
                          block_capacity=bcap)


@register
class EventEngine:
    name = "event"

    def build(self, c: Connectome, cfg) -> EventState:
        ow = c.out_weights
        if cfg.quantize_bits is not None:
            ow = quantize_weights(ow, cfg.quantize_bits)
        return EventState(
            out_indptr=jnp.asarray(c.out_indptr.astype(np.int32)),
            out_tgt=jnp.asarray(c.out_indices),
            out_w=jnp.asarray(ow.astype(np.float32)), n=c.n)

    def init_stats(self) -> dict:
        return {"slot_chunks": jnp.int32(0)}

    def deliver(self, state: EventState, spikes: jax.Array, cfg):
        g, dropped, _ = self.deliver_with_stats(state, spikes, cfg)
        return g, dropped

    def deliver_with_stats(self, state: EventState, spikes: jax.Array, cfg):
        """:meth:`deliver` plus the ``slot_chunks`` counter: the trips the
        slot loop ran this step."""
        n = state.n
        cap = cfg.capacity
        bcap = cap.block_capacity or derived_block_capacity(
            n, cap.spike_capacity)
        act_idx = two_level_active(spikes, cap.spike_capacity, bcap)
        g, total, chunks = deliver_ragged(
            act_idx, state.out_indptr, state.out_tgt, state.out_w,
            cap.syn_budget, invalid_from=n, n_out=n)
        requested = active_fanout_total(spikes, state.out_indptr)
        with jax.named_scope(scopes.DROP_COUNT):
            delivered = jnp.minimum(total, cap.syn_budget)
            dropped = requested - delivered
        return g, dropped, {"slot_chunks": chunks}
