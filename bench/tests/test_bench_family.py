"""A configuration brings its network, its required work and its readers as
new files: a second network family (a fixed in-degree random E/I network,
with one more per-synapse array than FlyWire's tables), its own generator,
reference, entry, ``work`` block and layer reader, written beside a copy of
the benchmark's files, runs through ``harness.run_cell`` with no existing
file changed."""

import hashlib
import json
import os
import shutil
import time

import numpy as np

from bench import harness, work
from bench.devtrace import Reduction
from tiny import SEED

GENERATOR = '''\
"""Network generator ``fixed_indegree``: every neuron receives exactly
``k_in`` synapses from distinct random sources (no self-synapses); a source
is inhibitory with probability ``frac_inhibitory`` (Dale's law), its
weights ``-w_inh`` or ``w_exc``.  Drawn on the host from the seed; the
tables carry the delay of every synapse in steps beside the weights."""

import dataclasses

import numpy as np

from bench import netgen, program


@dataclasses.dataclass(frozen=True)
class Network(netgen.Network):
    out_delays: np.ndarray = None     # [nnz] int32, steps


def generate(spec, seed):
    n, k = int(spec["n_neurons"]), int(spec["k_in"])
    rng = np.random.default_rng(seed)
    src = np.stack([rng.choice(np.delete(np.arange(n), t), k, replace=False)
                    for t in range(n)])
    inh = rng.random(n) < spec["frac_inhibitory"]
    w_src = np.where(inh, -int(spec["w_inh"]), int(spec["w_exc"]))
    pre, post = src.ravel(), np.repeat(np.arange(n), k)
    order = np.argsort(pre, kind="stable")
    out_indptr = np.concatenate([[0], np.cumsum(np.bincount(pre,
                                                            minlength=n))])
    return Network(
        n=n, out_indptr=out_indptr.astype(np.int64),
        out_indices=post[order].astype(np.int32),
        out_weights=w_src[pre[order]].astype(np.int32),
        in_indptr=np.arange(0, n * k + 1, k, dtype=np.int64),
        in_indices=pre.astype(np.int32),
        in_weights=w_src[pre].astype(np.int32),
        out_delays=np.full(n * k, int(spec["delay_steps"]), np.int32))


connectome = program.connectome
'''

ENTRY = '''\
"""Entry ``csr_simulate``: one run per call through ``repro.core.simulate``
with the configuration's engine (here the pull-based ``csr``)."""

import jax

from bench import program
from bench.compare import Answer

build = program.event_store


def call(store, stim_seed, lane_seeds):
    from repro.core import simulate
    (seed,) = lane_seeds
    stim = program.stimulus(store["conn"], store["cfg"], store["traffic"],
                            stim_seed)
    return simulate(store["conn"], store["cfg"],
                    int(store["traffic"]["steps"]), seed=seed,
                    syn=store["syn"], stimulus=stim)


def fetch(r):
    counts, (v, g, refrac), dropped, stats = jax.device_get(
        (r.counts, tuple(r.state), r.dropped, r.stats))
    return Answer(counts=counts[None], v=v[None], g=g[None],
                  refrac=refrac[None], dropped=dropped.reshape(1),
                  stats=program.counters(stats))
'''

REFERENCE = '''\
"""Plain reference of the fixed in-degree family: the same two-state LIF
and delay the flywire configurations run, on this family's tables."""

from bench.references.lif_network import run_call, run_control_call

__all__ = ["run_call", "run_control_call"]
'''

READER = '''\
"""``lif_ms_per_step``: device milliseconds per simulated step in the LIF
layer; nothing where no operation ran there."""


def read(m):
    return m.layers.get("lif") or None
'''

WORK = {"neuron_bytes": 34, "neuron_ops": 14, "event_bytes": 16,
        "event_ops": 1}


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def _family(tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(harness.BENCH, root, ignore=shutil.ignore_patterns(
        ".cache", "__pycache__", "tests"))
    before = _digest(root)
    with open(os.path.join(harness.BENCH, "configs", "flywire_q19.json")) as f:
        q19 = json.load(f)
    config = {**q19, "name": "er_q19", "reference": "fixed_indegree_lif",
              "network": {"generator": "fixed_indegree", "seed": 11,
                          "n_neurons": 400,
                          "k_in": 40, "frac_inhibitory": 0.2, "w_exc": 30,
                          "w_inh": 60, "delay_steps": 18},
              "model": {**q19["model"], "engine": "csr"}, "work": WORK}
    traffic = {"entry": "csr_simulate", "scenario": "sugar_feeding",
               "params": {"n_sugar": 40, "rate_hz": 150.0,
                          "background_hz": 0.0},
               "steps": 150, "lanes": 1, "capacity": "default"}
    new = {"networks/fixed_indegree.py": GENERATOR,
           "entries/csr_simulate.py": ENTRY,
           "references/fixed_indegree_lif.py": REFERENCE,
           "metrics/lif_ms_per_step.py": READER,
           "configs/er_q19.json": json.dumps(config),
           "traffic/er_sugar.json": json.dumps(traffic)}
    assert not set(new) & set(before)
    for rel, text in new.items():
        (root / rel).write_text(text)
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["end_to_end"] = [m for m in bench["end_to_end"]
                           if m["name"] in ("wall_per_bio_s", "setup_s")]
    for m in bench["end_to_end"]:
        m.pop("workloads", None)
    bench["per_layer"] = [{"name": "lif_ms_per_step", "unit": "ms",
                           "better": "lower", "source": "device_trace",
                           "layer": "LIF", "moves": "wall_per_bio_s"}]
    w = {"name": "er_sugar", "config": "er_q19", "traffic": "er_sugar",
         "chips": 1}
    return root, before, harness.cell_from(w, bench, root=str(root))


def test_second_family_runs_as_new_files(tmp_path):
    root, before, cell = _family(tmp_path)
    assert cell.entry.__name__ == "bench.entries.csr_simulate"
    net, conn = harness.network(cell)
    assert net.n == conn.n == 400 and net.nnz == 400 * 40
    assert (net.out_delays == 18).all()
    assert (np.diff(net.in_indptr) == 40).all()
    r = harness.run_cell(cell, SEED, 0.01, False, time.monotonic(),
                         require_tpu=False)
    assert r["correct"] is True, r["checks"]
    assert set(r["metrics"]) == {"wall_per_bio_s", "setup_s"}
    # the network did fire, so the comparison covered delivery
    want = cell.reference.run_call(net, cell.config["model"], cell.traffic,
                                   [1], 2)
    assert want.counts.sum() > 40
    after = _digest(root)
    assert {k: v for k, v in after.items() if k in before} == before


def test_second_family_reader_and_work(tmp_path):
    _, _, cell = _family(tmp_path)
    (spec, reader), = cell.per_layer
    red = Reduction(window_s=1.0, busy_s=0.5, collective_s=None, op_s={},
                    gaps=[])
    win = harness.Window(setup_s=1.0, window_s=1.0, calls=2, steps=150,
                         lanes=1, dt_ms=0.1, chips=1)
    m = work.Measure.of(red, win, cell.config["work"], 400, 1000,
                        type("K", (), {"device_kind": "TPU v5 lite"}),
                        layers={"lif": 0.02, "delivery": 0.1}, counters={})
    assert reader.read(m) == 0.02
    assert m.least_s == work.least_seconds(
        300 * 400 * 14 + 1000, 300 * 400 * 34 + 1000 * 16,
        work.peaks("TPU v5 lite"))
    empty = work.Measure.of(red, win, cell.config["work"], 400, 1000,
                            type("K", (), {"device_kind": "TPU v5 lite"}),
                            layers={}, counters={})
    assert reader.read(empty) is None
