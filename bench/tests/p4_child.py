"""Child process of ``test_bench_p4.py``: the partitioned cell at a tiny
size on 4 virtual CPU devices, sound and as its control (``sound``), or
with the exchange between chips left out (``no_exchange``, in a process of
its own so that no program compiled sound is reused).  Prints one JSON
line of results."""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(os.path.dirname(HERE))]

from tiny import ControlEntry, run, tiny_cell  # noqa: E402  (puts src on the path)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import repro.core.exchange.event as event  # noqa: E402
from repro.core.compaction import (derived_block_capacity,  # noqa: E402
                                   two_level_active)


def local_only(delayed, cap, topo):
    """The event exchange without the all-gather: each partition sees only
    its own spikes."""
    U, n_glob = topo.part_size, topo.n_global
    bcap = cap.block_capacity or derived_block_capacity(U, cap.spike_capacity)
    idx = two_level_active(delayed, cap.spike_capacity, bcap)
    my = jax.lax.axis_index(topo.axis)
    gid = jnp.where(idx < U, idx + my * U, n_glob).astype(jnp.int32)
    pad = jnp.full(((topo.n_parts - 1) * gid.shape[0],), n_glob, jnp.int32)
    return jnp.concatenate([gid, pad]), idx


out = {"devices": len(jax.devices())}
if sys.argv[1] == "sound":
    out["sound"] = run(tiny_cell("q19_p4_bg40"))
    cell = tiny_cell("q19_p4_bg40")
    cell.entry = ControlEntry(cell)
    out["control"] = run(cell)
else:
    event.gather_active_events = local_only
    out["no_exchange"] = run(tiny_cell("q19_p4_bg40"))
print(json.dumps({k: (v if k == "devices" else
                      {"correct": v["correct"], "checks": v["checks"],
                       "count": v["device"]["count"]})
                  for k, v in out.items()}))
