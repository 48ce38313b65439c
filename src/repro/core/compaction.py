"""Hierarchical active-set compaction and the shared bounded ragged gather.

The event-driven delivery paths (monolithic ``event`` engine and the
distributed ``event`` comm scheme) both reduce a boolean spike vector to a
fixed-capacity list of active indices and then ragged-gather those indices'
fan-out synapse runs into a bounded slot budget.  This module is the single
home for both primitives.

Why hierarchical compaction
---------------------------
``jnp.where(spikes, size=K)`` is an O(n) inclusive cumsum over the full
vector every step — at n=60k it dominates the sparse-activity step (~2.7 ms
of a ~4.5 ms step on CPU) even when only a handful of neurons spiked.
:func:`two_level_active` instead

1. reduces spikes to a per-block any-spike mask (``block`` = 128 lanes,
   matching the blocked engine's tile granularity) — a vectorized O(n)
   reduce, ~100x cheaper than the O(n) scan;
2. compacts the O(n/128) block ids with a bounded ``where`` over the mask;
3. compacts *within only the gathered active blocks* — a bounded ``where``
   over ``block_capacity * block`` elements.

Per-step compaction cost is O(n/B + B_cap·B) instead of O(n): sublinear in
n once activity (and hence ``block_capacity``) stops growing with it.

Capacity overruns — more active blocks than ``block_capacity``, more active
neurons than ``spike_capacity``, more fan-out synapses than the slot budget
— are never silent: callers combine :func:`active_fanout_total` (the exact
requested-synapse count) with the delivered count to report exact drops.

Slot->owner assignment
----------------------
``owner[s] = #{k : seg_end[k] <= s}`` equals
``searchsorted(seg_end, slot, side="right")`` but is computed by scattering
a unit bump at each segment end and taking an inclusive cumsum over the
slot window — O(C + K) sequential-friendly work instead of the
O(C · log K) gather-heavy probe per slot.

Activity-bounded slot loop
--------------------------
The slot budget ``S_cap`` is a static shape, but a step only has
``min(total, S_cap)`` synapses to deliver.  :func:`deliver_ragged` cuts
the budget into chunks of :func:`slot_chunk` slots and runs the
owner -> gather -> scatter work one chunk at a time in a
``lax.while_loop`` whose trip count, ``ceil(min(total, S_cap) / C)``, is
computed on the device: a step with no delayed spikes runs no trip, and
slot work is never more than ``min(total, S_cap) + C``.  The chunks visit
the slots in the same ascending order as one full-budget pass, so the
delivered set, and the drops, are unchanged.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.obs import scopes

BLOCK = 128   # compaction granularity; matches the blocked engine's tile
#: most chunks the slot loop cuts a synapse budget into (see slot_chunk)
SLOT_CHUNKS = 16


def n_blocks(n: int, block: int = BLOCK) -> int:
    """Number of ``block``-sized blocks covering ``n`` lanes (ceil div)."""
    return -(-n // block)


def derived_block_capacity(n: int, spike_capacity: int,
                           block: int = BLOCK) -> int:
    """Default block budget when a config leaves it 0: every active neuron
    could land in its own block, so ``spike_capacity`` blocks always
    suffice (capped at the total block count)."""
    return max(1, min(n_blocks(n, block), spike_capacity))


@jax.named_scope(scopes.COMPACTION)
def two_level_active(spikes: jnp.ndarray, spike_capacity: int,
                     block_capacity: int, block: int = BLOCK) -> jnp.ndarray:
    """Compact spiking indices into ``[spike_capacity]`` int32, ascending,
    with ``fill = n`` marking unused slots.

    Selection under overflow is hierarchical-prefix: the first
    ``block_capacity`` active blocks (by block id), then the first
    ``spike_capacity`` active neurons (by id) within those blocks.  With
    sufficient capacity this equals ``jnp.where(spikes, size=K, fill=n)``
    exactly; under overflow the kept set is still ascending and
    deterministic, so drop accounting stays exact and reproducible.
    """
    n = spikes.shape[0]
    nb = n_blocks(n, block)
    spp = jnp.pad(spikes, (0, nb * block - n)).reshape(nb, block)
    bmask = jnp.any(spp, axis=1)
    bids = jnp.where(bmask, size=block_capacity, fill_value=nb)[0]
    bids = bids.astype(jnp.int32)
    bvalid = bids < nb
    # gather only the active blocks; invalid slots contribute no spikes
    sub = jnp.logical_and(spp[jnp.minimum(bids, nb - 1)], bvalid[:, None])
    loc = jnp.where(sub.reshape(-1), size=spike_capacity,
                    fill_value=block_capacity * block)[0].astype(jnp.int32)
    lvalid = loc < block_capacity * block
    b = jnp.minimum(loc // block, block_capacity - 1)
    gid = bids[b] * block + loc % block
    return jnp.where(lvalid, gid, n).astype(jnp.int32)


def slot_chunk(syn_budget: int) -> int:
    """Slots per trip of the slot loop: whole 128-lane rows, at most
    :data:`SLOT_CHUNKS` trips per budget (4,096 at the default 65,536)."""
    return 128 * -(-syn_budget // (SLOT_CHUNKS * 128))


def slot_owner(seg_end: jnp.ndarray, window: int, lo=0) -> jnp.ndarray:
    """owner[j] = #{k : seg_end[k] <= lo + j} for j in [0, window) — equal
    to ``searchsorted(seg_end, lo + j, side="right")`` but computed by
    scattering a unit bump at each segment end (shifted by ``lo``: ends at
    or before ``lo`` count toward slot ``lo``) and taking an inclusive
    cumsum: O(window + K) instead of O(window · log K)."""
    bump = jnp.zeros(window + 1, jnp.int32).at[
        jnp.clip(seg_end - lo, 0, window)].add(1)
    return jnp.cumsum(bump[:window])


def fanout_segments(ids: jnp.ndarray, indptr: jnp.ndarray, *,
                    invalid_from: int):
    """``(starts, seg_end)``, each ``[K]``: where each valid id's fan-out
    run starts in the synapse store, and the inclusive cumsum of the run
    lengths (invalid ids, ``>= invalid_from``, have empty runs), so
    ``seg_end[-1]`` is the total synapse count the ids request."""
    valid = ids < invalid_from
    safe = jnp.minimum(ids, invalid_from - 1)
    starts = jnp.where(valid, indptr[safe], 0)
    lens = jnp.where(valid, indptr[safe + 1] - indptr[safe], 0)
    return starts, jnp.cumsum(lens)


def window_slots(starts, seg_end, syn_budget: int, lo, window: int,
                 gather_size: int):
    """Gather indices and validity of budget slots ``[lo, lo + window)``
    given :func:`fanout_segments`' runs; slots at or beyond
    ``min(total, syn_budget)`` are invalid."""
    k = seg_end.shape[0]
    owner = jnp.minimum(slot_owner(seg_end, window, lo), k - 1)
    prev_end = jnp.where(owner > 0, seg_end[owner - 1], 0)
    slot = lo + jnp.arange(window, dtype=jnp.int32)
    syn_ix = jnp.clip(starts[owner] + slot - prev_end, 0, gather_size - 1)
    ok = slot < jnp.minimum(seg_end[-1], syn_budget)
    return syn_ix, ok


@jax.named_scope(scopes.SLOT_OWNER)
def ragged_slots(ids: jnp.ndarray, indptr: jnp.ndarray, syn_budget: int, *,
                 invalid_from: int, gather_size: int, lo=0,
                 window: int | None = None):
    """Assign the fan-out synapse runs of compacted ``ids`` to a bounded
    flat slot budget, one window of it at a time.

    ``ids`` is a ``[K]`` compacted index list (from
    :func:`two_level_active` or an all-gather of such lists) where any
    value ``>= invalid_from`` marks an unused slot.  ``indptr`` is the
    ``[invalid_from + 1]`` CSR row-pointer array of the synapse store the
    caller will gather from; ``gather_size`` bounds the produced indices
    (the store's first-axis length).  ``lo`` (may be traced) and the
    static ``window`` (default: the whole budget) select budget slots
    ``[lo, lo + window)``.

    Returns ``(syn_ix [window] i32, ok [window] bool, total i32)``: gather
    indices per slot, slot validity, and the total synapse count requested
    by the valid ids (``total - min(total, syn_budget)`` synapses were
    dropped to the budget).  Cost: O(window + K), independent of the
    store size.
    """
    starts, seg_end = fanout_segments(ids, indptr, invalid_from=invalid_from)
    syn_ix, ok = window_slots(starts, seg_end, syn_budget, lo,
                              window or syn_budget, gather_size)
    return syn_ix, ok, seg_end[-1]


def deliver_ragged(ids: jnp.ndarray, indptr: jnp.ndarray, tgt: jnp.ndarray,
                   w: jnp.ndarray, syn_budget: int, *, invalid_from: int,
                   n_out: int):
    """Deliver the fan-out synapses of compacted ``ids`` into ``[n_out]``
    targets, up to ``syn_budget`` of them in ascending slot order — the
    activity-bounded slot loop (module docstring).

    ``indptr``/``tgt``/``w`` are a source-major synapse store (row
    pointers over ``invalid_from`` sources, targets in ``[0, n_out)``,
    weights).  Returns ``(g [n_out], total i32, chunks_run i32)``: the
    summed weights per target, the synapse count the ids requested, and
    the trips the loop ran, ``ceil(min(total, syn_budget) / C)``.
    """
    window = slot_chunk(syn_budget)
    with jax.named_scope(scopes.SLOT_OWNER):
        starts, seg_end = fanout_segments(ids, indptr,
                                          invalid_from=invalid_from)
        total = seg_end[-1]
        trips = (jnp.minimum(total, syn_budget) + window - 1) // window

    def chunk(carry):
        i, g = carry
        with jax.named_scope(scopes.SLOT_OWNER):
            syn_ix, ok = window_slots(starts, seg_end, syn_budget,
                                      i * window, window, tgt.shape[0])
        with jax.named_scope(scopes.SYN_GATHER):
            contrib = jnp.where(ok, w[syn_ix], 0)
            t = jnp.where(ok, tgt[syn_ix], n_out)
        with jax.named_scope(scopes.SYN_SCATTER):
            g = g.at[t].add(contrib)
        return i + 1, g

    g0 = jnp.zeros(n_out + 1, w.dtype)
    _, g = jax.lax.while_loop(lambda c: c[0] < trips, chunk,
                              (jnp.int32(0), g0))
    return g[:n_out], total, trips


@jax.named_scope(scopes.DROP_COUNT)
def active_fanout_total(spikes: jnp.ndarray, indptr: jnp.ndarray):
    """Exact number of synapses the spike vector *requests* — the
    drop-accounting ground truth (requested - delivered = dropped), immune
    to what the bounded compaction kept.  One vectorized O(n) multiply-add,
    no scan/scatter."""
    fo = indptr[1:] - indptr[:-1]
    return jnp.sum(jnp.where(spikes, fo, 0))


__all__ = ["BLOCK", "SLOT_CHUNKS", "active_fanout_total", "deliver_ragged",
           "derived_block_capacity", "fanout_segments", "n_blocks",
           "ragged_slots", "slot_chunk", "slot_owner", "two_level_active",
           "window_slots"]
