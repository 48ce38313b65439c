"""The step's layer scopes (``repro.obs.scopes``) reach the compiled
program: every HLO operation's ``op_name`` carries the layer it belongs to,
on the monolithic scan, the vmapped trials scan and the P=4 ``shard_map``
scan (4 virtual CPU devices, in a subprocess)."""

import os
import re
import subprocess
import sys
import textwrap

import jax.extend as jex
import pytest

from repro.configs.flywire import SMOKE
from repro.core import build_synapses, synthetic_flywire
from repro.core.engine import _init_carry, _run_scan_jit, _run_scan_trials_jit
from repro.exp import ProbeSpec, build_scenario
from repro.exp.trials import trial_carry
from repro.obs import scopes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROBES = ProbeSpec(pop_rate=True)     # a probe that computes something
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_components(hlo_text: str) -> set:
    """Every named-scope component on the ops' ``op_name`` paths (the last
    component, the primitive, left out)."""
    found = set()
    for op_name in _OP_NAME.findall(hlo_text):
        found.update(op_name.split("/")[:-1])
    return found


@pytest.fixture(scope="module")
def smoke():
    c = synthetic_flywire(n=SMOKE.n_neurons,
                          target_synapses=SMOKE.target_synapses, seed=0)
    cfg = SMOKE.sim
    stim = build_scenario("sugar_feeding", c, cfg, n_sugar=SMOKE.n_sugar,
                          rate_hz=SMOKE.sugar_rate_hz)
    return c, cfg, stim, build_synapses(c, cfg)


def test_scope_names_are_not_primitives():
    prims = {v.name for v in vars(jex.core.primitives).values()
             if isinstance(v, jex.core.Primitive)}
    assert len(prims) > 50
    assert not set(scopes.SCOPES) & prims
    assert len(set(scopes.SCOPES)) == len(scopes.SCOPES)
    assert scopes.DELIVERY in scopes.LAYERS
    assert not set(scopes.DELIVERY_PARTS) & set(scopes.LAYERS)


# the monolithic step's exchange is the identity: no op to carry its name
P1_SCOPES = set(scopes.SCOPES) - {scopes.EXCHANGE}


def test_monolithic_scan_carries_every_layer(smoke):
    c, cfg, stim, syn = smoke
    text = _run_scan_jit.lower(syn, _init_carry(c.n, cfg, stim, 0), stim,
                               cfg, PROBES, 20, c.n).compile().as_text()
    assert P1_SCOPES <= scope_components(text)


def test_trials_scan_carries_every_layer(smoke):
    c, cfg, stim, syn = smoke
    carry, _ = trial_carry(c.n, cfg, stim, (1, 2))
    text = _run_scan_trials_jit.lower(syn, carry, stim, cfg, PROBES, 20,
                                      c.n).compile().as_text()
    assert P1_SCOPES <= scope_components(text)


P4_SCRIPT = textwrap.dedent("""
    import os, re, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    from repro.configs.flywire import SMOKE
    from repro.core import synthetic_flywire
    from repro.core import distributed as D
    from repro.core.dcsr import build_dcsr
    from repro.core.exchange import get_scheme
    from repro.core.partition import even_partition
    from repro.exp import ProbeSpec, build_scenario

    c = synthetic_flywire(n=SMOKE.n_neurons,
                          target_synapses=SMOKE.target_synapses, seed=0)
    cfg = SMOKE.sim
    stim = build_scenario("sugar_feeding", c, cfg, n_sugar=SMOKE.n_sugar,
                          rate_hz=SMOKE.sugar_rate_hz)
    dcfg = D.DistConfig(sim=cfg, scheme="event")
    d = build_dcsr(c, even_partition(c, 4), quantize_bits=cfg.quantize_bits)
    scheme = get_scheme("event")
    state = scheme.build(d, cfg, dcfg.capacity)
    st = D._resolve_dist_stimulus(d, cfg, None, stim)
    probes, vrows, _ = D._resolve_dist_probes(d, cfg,
                                              ProbeSpec(pop_rate=True))
    pad = jnp.asarray(d.inv_perm.reshape(d.n_parts, d.part_size) >= 0)
    keys = jax.random.split(jax.random.PRNGKey(0), d.n_parts)
    carry = D._init_dist_carry(d, dcfg, st, scheme, keys)
    fn = D._shard_map_fn("event", dcfg, probes, 20, False,
                         D.make_core_mesh(4), 4, d.part_size)
    text = fn.fn.lower(carry, state, st, pad, vrows,
                       jnp.int32(0)).compile().as_text()
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        found.update(op_name.split("/")[:-1])
    gathers = re.findall(r'all-gather[^\\n]*op_name="([^"]*)"', text)
    print("SCOPES", " ".join(sorted(found)))
    print("ALLGATHER", " ".join(gathers))
""")


def test_shard_map_scan_carries_every_layer(tmp_path):
    script = tmp_path / "p4_scopes.py"
    script.write_text(P4_SCRIPT)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = dict(line.split(" ", 1) for line in out.stdout.splitlines()
                 if line.startswith(("SCOPES ", "ALLGATHER ")))
    assert set(scopes.SCOPES) <= set(lines["SCOPES"].split())
    # the collective is the exchange's, and only the exchange's
    gathers = lines["ALLGATHER"].split()
    assert gathers and all(f"/{scopes.EXCHANGE}/" in g for g in gathers)
