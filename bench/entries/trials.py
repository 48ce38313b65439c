"""Entry ``trials``: one vmapped batch of lanes per call through
``repro.exp.run_trials`` (one seed per lane, no chunking)."""

from __future__ import annotations

import jax

from bench import program
from bench.compare import Answer


build = program.event_store


def call(store: dict, stim_seed: int, lane_seeds: list[int]):
    from repro.exp import run_trials
    stim = program.stimulus(store["conn"], store["cfg"], store["traffic"],
                            stim_seed)
    return run_trials(store["conn"], store["cfg"],
                      int(store["traffic"]["steps"]), seeds=list(lane_seeds),
                      syn=store["syn"], stimulus=stim)


def fetch(r) -> Answer:
    counts, (v, g, refrac), dropped = jax.device_get(
        (r.counts, tuple(r.state), r.dropped))
    return Answer(counts=counts, v=v, g=g, refrac=refrac, dropped=dropped)
