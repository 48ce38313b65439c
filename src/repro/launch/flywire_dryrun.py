import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""FlyWire connectome simulation dry-run on the production mesh — the
paper's own workload mapped onto 256/512 TPU cores.

    PYTHONPATH=src python -m repro.launch.flywire_dryrun \
        [--cores 256|512] [--scale bench|full] [--scheme event|bitmap]

Pipeline: synthetic FlyWire graph -> greedy SAR capacity partitioning ->
pad to the mesh core count -> SNN-dCSR -> lower + compile the shard_map
event-driven simulation step (scan over one delay window) on a flat
device mesh.  Records the same memory/cost/collective analysis as the LM
dry-run (JSON to experiments/dryrun/).
"""

import argparse        # noqa: E402
import functools       # noqa: E402
import json            # noqa: E402
import time            # noqa: E402

import jax             # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np     # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs.flywire import CONFIG, SMOKE             # noqa: E402
from repro.core import (CoreBudget, caps_from_budget,       # noqa: E402
                        greedy_partition, synthetic_flywire_cached)
from repro.core.dcsr import build_dcsr                      # noqa: E402
from repro.core.distributed import AXIS, DistConfig         # noqa: E402
from repro.core.exchange import (DistArrays, Topology,      # noqa: E402
                                 get_scheme)
from repro.core.partition import pad_to_uniform             # noqa: E402
from repro.core.step import SimCarry, scan_steps            # noqa: E402
from repro.launch.hlo import analyze_hlo                    # noqa: E402
from repro.launch.mesh import make_flat_mesh                # noqa: E402


def abstract_dist_arrays(d, n_glob):
    """ShapeDtypeStruct stand-ins for DistArrays (no host materialization
    of the regrouped event-scheme structures needed to lower)."""
    Pn, U, S = d.n_parts, d.part_size, d.s_max
    f32 = jnp.float32
    i32 = jnp.int32
    sd = jax.ShapeDtypeStruct
    return DistArrays(
        syn_src=sd((Pn, S), i32), syn_tgt=sd((Pn, S), i32),
        syn_w=sd((Pn, S), f32),
        out_indptr=sd((Pn, n_glob + 1), i32),
        out_tgt=sd((Pn, S), i32), out_w=sd((Pn, S), f32),
        pad_mask=sd((Pn, U), jnp.bool_),
        src_gfo=sd((Pn, U), i32),
    )


def abstract_stimulus(sim, Pn, U):
    """The legacy masked sugar+background stimulus with abstract [P, U]
    mask leaves (same pytree the concrete shard_stimulus path produces)."""
    from repro.exp.stimulus import legacy_stimulus
    stim = legacy_stimulus(sim, Pn * U, masked=True).to_masked(Pn * U)
    sd = jax.ShapeDtypeStruct
    return jax.tree.map(lambda _: sd((Pn, U), jnp.bool_), stim)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cores", type=int, default=256)
    ap.add_argument("--scale", choices=["bench", "full"], default="full")
    ap.add_argument("--scheme", choices=["event", "bitmap"], default="event")
    ap.add_argument("--steps", type=int, default=18,
                    help="steps per lowered scan (one 1.8ms delay window)")
    ap.add_argument("--capacity", type=int, default=256,
                    help="event capacity K per core per step (provisioned "
                         "activity — the Loihi 'cost ~ spikes' lever)")
    ap.add_argument("--budget", type=int, default=65536,
                    help="synapse delivery budget per core per step")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    fw = CONFIG if args.scale == "full" else SMOKE
    n, syn = ((fw.n_neurons, fw.target_synapses) if args.scale == "full"
              else (20_000, 600_000))
    t0 = time.time()
    c = synthetic_flywire_cached(n=n, seed=0, target_synapses=syn)
    p = greedy_partition(c, caps_from_budget(CoreBudget.tpu_vmem(), "sar"),
                         scheme="sar")
    p = pad_to_uniform(p, args.cores, c.n)
    d = build_dcsr(c, p, quantize_bits=9)
    print(f"[flywire-dryrun] graph {c.n}n/{c.nnz}syn -> {d.n_parts} cores, "
          f"U={d.part_size}, S_max={d.s_max} "
          f"(prep {time.time()-t0:.0f}s)")

    mesh = make_flat_mesh(args.cores)
    from repro.core.capacity import CapacityConfig
    cfg = DistConfig(sim=fw.sim, scheme=args.scheme,
                     capacity=CapacityConfig(spike_capacity=args.capacity,
                                             syn_budget=args.budget))
    Pn, U = d.n_parts, d.part_size
    arrs = abstract_dist_arrays(d, Pn * U)
    stim = abstract_stimulus(fw.sim, Pn, U)
    from repro.core.neuron import LIFState
    from repro.exp.probes import NO_PROBES
    sd = jax.ShapeDtypeStruct
    keys_aval = jax.eval_shape(
        lambda: jax.random.split(jax.random.PRNGKey(0), Pn))
    scheme = get_scheme(args.scheme)
    carry = SimCarry(
        lif=LIFState(v=sd((Pn, U), jnp.int32), g=sd((Pn, U), jnp.int32),
                     refrac=sd((Pn, U), jnp.int32)),
        ring=sd((Pn, fw.sim.params.delay_steps, U), jnp.bool_),
        ptr=sd((Pn,), jnp.int32),
        key=keys_aval,
        counts=sd((Pn, U), jnp.int32),
        dropped=sd((Pn,), jnp.int32),
        # state structure must match the stimulus (Compose.step zips them)
        stim=stim.init_state(U),
        stats=scheme.init_stats(),
    )
    topo = Topology(Pn, U, axis=AXIS)

    def run_window(carry_in, arr, st):
        carry_in = jax.tree.map(lambda x: x[0], carry_in)
        arr = jax.tree.map(lambda x: x[0], arr)
        st = jax.tree.map(lambda x: x[0], st)
        cc, _ = scan_steps(scheme, arr, carry_in, st, cfg.sim, cfg.capacity,
                           topo, NO_PROBES, args.steps,
                           pad_mask=arr.pad_mask)
        return jax.tree.map(lambda x: x[None], cc)

    spec_c = jax.tree.map(lambda _: P("cores"), carry)
    spec_a = jax.tree.map(lambda _: P("cores"), arrs)
    spec_s = jax.tree.map(lambda _: P("cores"), stim)
    fn = jax.shard_map(run_window, mesh=mesh,
                       in_specs=(spec_c, spec_a, spec_s), out_specs=spec_c,
                       check_vma=False)
    sh_c = jax.tree.map(lambda s: NamedSharding(mesh, s), spec_c)
    sh_a = jax.tree.map(lambda s: NamedSharding(mesh, s), spec_a)
    sh_s = jax.tree.map(lambda s: NamedSharding(mesh, s), spec_s)

    t1 = time.time()
    with mesh:
        lowered = jax.jit(fn, in_shardings=(sh_c, sh_a, sh_s),
                          donate_argnums=0).lower(carry, arrs, stim)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    hlo = analyze_hlo(compiled.as_text())
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    rec = {
        "arch": "flywire-snn", "cell": f"{args.scale}_{args.scheme}",
        "mesh": f"cores{args.cores}", "n_devices": args.cores,
        "kind": "simulate", "steps_per_window": args.steps,
        "compile_s": round(time.time() - t1, 1),
        "memory": {"argument_bytes": mem.argument_size_in_bytes,
                   "temp_bytes": mem.temp_size_in_bytes,
                   "peak_device_bytes": peak},
        "cost": {"flops_per_device": hlo.flops,
                 "bytes_per_device": hlo.bytes},
        "collectives": hlo.summary(),
    }
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(
            args.out, f"flywire_{args.scale}_{args.scheme}_"
            f"c{args.cores}_k{args.capacity}.json"), "w") as f:
        json.dump(rec, f, indent=1)
    # roofline terms for one delay window (18 steps of 0.1 ms)
    comp = hlo.flops / 197e12
    memt = hlo.bytes / 819e9
    coll = hlo.link_bytes / 50e9
    print(f"[flywire-dryrun] compile {rec['compile_s']}s  "
          f"peak/core {peak/2**20:.1f} MiB  "
          f"window terms: compute {comp*1e6:.1f}us  "
          f"memory {memt*1e6:.1f}us  collective {coll*1e6:.1f}us  "
          f"counts {hlo.coll_count}")
    print("  memory_analysis:", mem)
    sim_window_ms = args.steps * fw.sim.params.dt
    bound = max(comp, memt, coll)
    print(f"[flywire-dryrun] modelled wall/window {bound*1e3:.3f} ms vs "
          f"simulated {sim_window_ms:.1f} ms -> "
          f"{sim_window_ms/1e3/bound:.0f}x faster than realtime (model)")


if __name__ == "__main__":
    main()
