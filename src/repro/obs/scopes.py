"""Names of the simulation step's layers on the device.

Each name is a :func:`jax.named_scope` around one layer of the step body
(:mod:`repro.core.step` and the primitives it calls), so every HLO
operation's ``op_name`` metadata carries the layer it belongs to — on the
monolithic, vmapped-trials and ``shard_map`` paths alike, because the
scopes sit in the shared primitives.  A profiler trace of a compiled run
can then be split by layer: an operation belongs to the innermost of
these names on its ``op_name`` path (the last path component, the JAX
primitive, is never read), and operations under none of them — the
scan's loop control and per-call set-up outside the scan — are "other".

None of the names equals a JAX primitive name (hence ``syn_gather``, not
``gather``), so a scope is never mistaken for the primitive it wraps.

* ``stimulus``: the key split and ``stim.step`` (Poisson drive,
  background RNG);
* ``exchange``: ``scheme.exchange`` (the identity at P=1, the all-gather
  of compacted spike ids across partitions);
* ``compaction``: ``two_level_active`` (block any-reduce, bounded block
  and in-block ``where``);
* ``delivery``: ``scheme.deliver`` / ``deliver_fused``, with four parts:
  ``slot_owner`` (``ragged_slots``: owner bump, cumsum, slot indices),
  ``syn_gather`` (the synapse target and weight gathers), ``syn_scatter``
  (``segment_sum`` into the targets), ``drop_count`` (exact drop
  accounting);
* ``lif``: the LIF update, float or Q19.12, and pad masking;
* ``ring``: delay-ring read and write, pointer, counts, drops, scheme and
  health counters;
* ``probes``: in-scan probe records.
"""

from __future__ import annotations

STIMULUS = "stimulus"
EXCHANGE = "exchange"
COMPACTION = "compaction"
DELIVERY = "delivery"
SLOT_OWNER = "slot_owner"
SYN_GATHER = "syn_gather"
SYN_SCATTER = "syn_scatter"
DROP_COUNT = "drop_count"
LIF = "lif"
RING = "ring"
PROBES = "probes"

#: the step's layers, each timed on its own
LAYERS = (STIMULUS, EXCHANGE, COMPACTION, DELIVERY, LIF, RING, PROBES)
#: parts of ``delivery``: listed on their own, rolled up into it
DELIVERY_PARTS = (SLOT_OWNER, SYN_GATHER, SYN_SCATTER, DROP_COUNT)
SCOPES = LAYERS + DELIVERY_PARTS

__all__ = ["COMPACTION", "DELIVERY", "DELIVERY_PARTS", "DROP_COUNT",
           "EXCHANGE", "LAYERS", "LIF", "PROBES", "RING", "SCOPES",
           "SLOT_OWNER", "STIMULUS", "SYN_GATHER", "SYN_SCATTER"]
