"""Delivery-engine registry: one module per synaptic-delivery strategy.

Importing this package registers every built-in engine:

======== ==================================================================
dense    naive W @ s matmul (test-scale oracle)
ell      target-major padded gather, SSD fan-in cap (paper §3.2.4)
csr      flat segment-sum over all synapses (conventional baseline)
event    active-set event-driven scatter (Loihi-like, cost ∝ activity)
binned   SAR bin-compressed histogram delivery (paper §3.2.3)
blocked  block-gated Pallas kernel, cost ∝ live 128x128 tiles (TPU-native)
blocked_fused  blocked delivery + LIF integration fused in one kernel:
         delivered currents and the tile-skip mask never leave VMEM
         (``integrates_lif`` capability — the step body skips its own
         LIF update)
======== ==================================================================

See ``docs/engines.md`` for the comparison matrix and
:mod:`repro.core.engines.base` for the :class:`DeliveryEngine` protocol.
"""

from .base import (DeliveryEngine, available_engines, engine_init_stats,
                   engine_integrates_lif, get_engine, register,
                   register_state, static_field)
from . import (binned, blocked, blocked_fused, csr, dense, ell,  # noqa: F401
               event)
from .binned import BinnedEngine, BinnedState
from .blocked import BlockedEngine, BlockedState
from .blocked_fused import BlockedFusedEngine
from .csr import CsrEngine, CsrState
from .dense import DenseEngine, DenseState
from .ell import EllEngine, EllState
from .event import Capacity, EventEngine, EventState, auto_capacity

__all__ = [
    "DeliveryEngine", "available_engines", "engine_init_stats",
    "engine_integrates_lif",
    "get_engine", "register", "register_state", "static_field", "Capacity",
    "auto_capacity", "BinnedEngine", "BinnedState", "BlockedEngine",
    "BlockedFusedEngine", "BlockedState", "CsrEngine", "CsrState",
    "DenseEngine", "DenseState", "EllEngine", "EllState", "EventEngine",
    "EventState",
]
