"""The benchmark's side of the simulator's public API: how a configuration
and a traffic file become the simulator's own objects.

This is the one module outside ``entries/`` that imports the system under
test; the entries drive its public entry points.
"""

from __future__ import annotations

import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def import_program():
    """Put the checkout's ``src`` on the path; fails outside a checkout."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"bench: the simulator is not in this checkout "
                         f"({src}/repro not found)")
    if src not in sys.path:
        sys.path.insert(0, src)


def connectome(net):
    """The simulator's ``Connectome`` over the benchmark's generated tables."""
    from repro.core import Connectome
    return Connectome(n=net.n, in_indptr=net.in_indptr,
                      in_indices=net.in_indices, in_weights=net.in_weights,
                      out_indptr=net.out_indptr, out_indices=net.out_indices,
                      out_weights=net.out_weights)


def counters(stats: dict) -> dict:
    """The integer counters of a result's ``stats`` (engine, scheme and
    health counters) as host arrays; any other entry, such as the
    telemetry's ``compile_cache`` snapshot, is left out."""
    arrays = {k: np.asarray(v) for k, v in stats.items()}
    return {k: a for k, a in arrays.items()
            if np.issubdtype(a.dtype, np.integer)}


def capacity(traffic: dict):
    """The traffic's event budgets; ``None`` keeps the entry's default."""
    cap = traffic["capacity"]
    if cap == "default":
        return None
    from repro.core import CapacityConfig
    return CapacityConfig(spike_capacity=int(cap["spike_capacity"]),
                          syn_budget=int(cap["syn_budget"]),
                          block_capacity=int(cap["block_capacity"]))


def sim_config(model: dict, traffic: dict):
    from repro.core import LIFParams, SimConfig
    kw = {}
    cap = capacity(traffic)
    if cap is not None:
        kw["capacity"] = cap
    return SimConfig(params=LIFParams(**model["lif"]), engine=model["engine"],
                     fixed_point=bool(model["fixed_point"]),
                     quantize_bits=model.get("quantize_bits"),
                     poisson_to_v=bool(model["poisson_to_v"]),
                     poisson_weight=float(model["poisson_weight"]), **kw)


def event_store(conn, config: dict, traffic: dict) -> dict:
    """What a one-chip entry reuses across calls: the simulator's config
    and its device synapse store (``build_synapses``)."""
    from repro.core import build_synapses
    cfg = sim_config(config["model"], traffic)
    return {"conn": conn, "cfg": cfg, "traffic": traffic,
            "syn": build_synapses(conn, cfg)}


def stimulus(conn, cfg, traffic: dict, stim_seed: int):
    """The traffic's named scenario; a scenario that picks a population
    draws it from the traffic's own ``seed`` (a fixed population, the same
    in every run) or, where the traffic gives none, from ``stim_seed``."""
    from repro.exp import build_scenario, get_scenario
    params = dict(traffic["params"])
    if "seed" in get_scenario(traffic["scenario"]).defaults:
        params.setdefault("seed", int(stim_seed))
    return build_scenario(traffic["scenario"], conn, cfg, **params)


__all__ = ["ROOT", "capacity", "connectome", "counters", "event_store",
           "import_program", "sim_config", "stimulus"]
