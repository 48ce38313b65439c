"""Entry ``simulate``: one run per call through ``repro.core.simulate``, the
monolithic jitted scan (no chunking, no telemetry)."""

from __future__ import annotations

import jax

from bench import program
from bench.compare import Answer


build = program.event_store


def call(store: dict, stim_seed: int, lane_seeds: list[int]):
    from repro.core import simulate
    (seed,) = lane_seeds
    stim = program.stimulus(store["conn"], store["cfg"], store["traffic"],
                            stim_seed)
    return simulate(store["conn"], store["cfg"], int(store["traffic"]["steps"]),
                    seed=seed, syn=store["syn"], stimulus=stim)


def fetch(r) -> Answer:
    counts, (v, g, refrac), dropped, stats = jax.device_get(
        (r.counts, tuple(r.state), r.dropped, r.stats))
    return Answer(counts=counts[None], v=v[None], g=g[None],
                  refrac=refrac[None], dropped=dropped.reshape(1),
                  stats=program.counters(stats))
