"""Entry ``distributed``: one partitioned run per call through
``repro.core.distributed.simulate_distributed`` over a mesh of one
partition per device (shard_map), the configuration's exchange scheme and
partitioning; results come back in original neuron ids."""

from __future__ import annotations

import numpy as np

from bench import program
from bench.compare import Answer


def build(conn, config: dict, traffic: dict) -> dict:
    from repro.core import even_partition
    from repro.core.dcsr import build_dcsr
    from repro.core.distributed import DistConfig, make_core_mesh
    part = config["partition"]
    if part["rule"] != "even":
        raise ValueError(f"unknown partition rule {part['rule']!r}")
    parts = int(part["parts"])
    sim = program.sim_config(config["model"], {**traffic,
                                               "capacity": "default"})
    cap = program.capacity(traffic)
    dcfg = DistConfig(sim=sim, scheme=part["scheme"],
                      **({} if cap is None else {"capacity": cap}))
    d = build_dcsr(conn, even_partition(conn, parts),
                   quantize_bits=sim.quantize_bits,
                   lane_multiple=int(part["pad_multiple"]))
    return {"conn": conn, "cfg": sim, "dcfg": dcfg, "dcsr": d,
            "traffic": traffic, "mesh": make_core_mesh(parts)}


def call(store: dict, stim_seed: int, lane_seeds: list[int]):
    from repro.core.distributed import simulate_distributed
    (seed,) = lane_seeds
    stim = program.stimulus(store["conn"], store["cfg"], store["traffic"],
                            stim_seed)
    return simulate_distributed(store["dcsr"], store["dcfg"],
                                int(store["traffic"]["steps"]), seed=seed,
                                mesh=store["mesh"], stimulus=stim)


def fetch(r) -> Answer:
    v, g, refrac = (np.asarray(x) for x in r.state)
    return Answer(counts=np.asarray(r.counts)[None], v=v[None], g=g[None],
                  refrac=refrac[None], dropped=np.asarray([r.dropped]),
                  stats=program.counters(r.stats))
