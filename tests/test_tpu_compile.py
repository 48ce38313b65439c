"""The main path's device programs compile for a TPU v5e.

Interpret mode accepts block shapes that the TPU compiler (Mosaic)
refuses, so the Pallas kernels are compiled here for a *described* v5e
chip, with no chip attached: the two spike-delivery kernels at bench
width (20,000 neurons -> 157 x 157 tiles) in float32 and Q19.12, and the
event-engine scan that ``FlyWireConfig`` runs, at ``SMOKE`` size.  Nothing
runs; a refused layout or an over-budget kernel raises at ``compile()``.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and the test workers each import this
file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.flywire import CONFIG, SMOKE
from repro.core import build_synapses, synthetic_flywire
from repro.core.engine import _init_carry, _run_scan_jit
from repro.exp import ProbeSpec, build_scenario
from repro.kernels.spike_prop.kernel import (fused_deliver_lif_pallas,
                                             spike_deliver_pallas)

N_TB = E = 157            # ceil(20,000 / 128): bench-width tile grid


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A chip sharding, with the persistent compilation cache off: entries
    compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tile_operands(sh):
    return (_spec((N_TB, E), jnp.int32, sh),
            _spec((N_TB, E, 128, 128), jnp.float32, sh),
            _spec((N_TB + 1, 128), jnp.float32, sh))


def test_spike_deliver_compiles_for_v5e(one_chip):
    blk, w, spk = _tile_operands(one_chip)
    nspk = _spec((N_TB + 1,), jnp.int32, one_chip)
    compiled = jax.jit(
        lambda *a: spike_deliver_pallas(*a, interpret=False)
    ).lower(blk, w, spk, nspk).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("fx", [False, True], ids=["f32", "q19.12"])
def test_fused_kernel_compiles_for_v5e(one_chip, fx):
    blk, w, spk = _tile_operands(one_chip)
    sdt = jnp.int32 if fx else jnp.float32
    row = lambda dt: _spec((N_TB, 128), dt, one_chip)  # noqa: E731

    def step(blk, w, spk, v, g, refrac, gstim, vin, force):
        return fused_deliver_lif_pallas(
            blk, w, spk, v, g, refrac, gstim, vin, force,
            params=CONFIG.sim.params, fixed_point=fx, interpret=False)

    compiled = jax.jit(step).lower(
        blk, w, spk, row(sdt), row(sdt), row(jnp.int32), row(jnp.float32),
        row(sdt), row(jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_event_scan_compiles_for_v5e(one_chip):
    """The paper configuration's whole scan (event engine, Q19.12)."""
    c = synthetic_flywire(n=SMOKE.n_neurons,
                          target_synapses=SMOKE.target_synapses, seed=0)
    cfg = SMOKE.sim
    stim = build_scenario("sugar_feeding", c, cfg, n_sugar=SMOKE.n_sugar,
                          rate_hz=SMOKE.sugar_rate_hz)
    args = (build_synapses(c, cfg), _init_carry(c.n, cfg, stim, 0), stim)
    specs = jax.tree.map(
        lambda x: _spec(np.shape(x), x.dtype, one_chip), args)
    compiled = _run_scan_jit.lower(*specs, cfg, ProbeSpec(), SMOKE.t_steps,
                                   c.n).compile()
    assert compiled.memory_analysis().argument_size_in_bytes > 0
