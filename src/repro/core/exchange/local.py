"""Local scheme: the degenerate P=1 exchange (no collectives).

The monolithic simulation loop is the single-partition case of the
paper's model of computation: every neuron lives on one "core", so the
spike exchange is the identity and delivery is whatever the registered
delivery engine (:mod:`repro.core.engines`, ``SimConfig.engine``) does.
Routing ``simulate()`` through this scheme is what lets the monolithic
and distributed entry points share one step body verbatim
(:mod:`repro.core.step`).
"""

from __future__ import annotations

from .base import Topology, register_scheme


@register_scheme
class LocalExchange:
    name = "local"

    def build(self, c, sim, cap):
        from ..engines import get_engine
        return get_engine(sim.engine).build(c, sim)

    def init_stats(self) -> dict:
        return {}

    def exchange(self, state, delayed, cap, topo: Topology):
        return delayed

    def deliver(self, state, payload, delayed, sim, cap, topo: Topology):
        from ..engines import get_engine
        engine = get_engine(sim.engine)
        counted = getattr(engine, "deliver_with_stats", None)
        if counted is not None:
            return counted(state, payload, sim)
        g, drop = engine.deliver(state, payload, sim)
        return g, drop, {}

    # -- fused-integration capability: delegated to the engine registry --

    def fuses_lif(self, sim) -> bool:
        from ..engines import engine_integrates_lif
        return engine_integrates_lif(sim.engine)

    def deliver_fused(self, state, payload, delayed, lif, drive, sim, cap,
                      topo: Topology):
        from ..engines import get_engine
        new_lif, spikes, drop = get_engine(sim.engine).deliver_fused(
            state, payload, lif, drive, sim)
        return new_lif, spikes, drop, {}
