"""Public jit'd wrappers for the fused LIF kernel.

Handles padding to the [rows, 128] kernel layout from flat [n] state and
dispatches to the float32 or fixed-point kernel.  The simulator never
calls this kernel (the fused delivery kernel calls
:mod:`repro.core.neuron`'s step directly); only tests/test_kernels.py
runs it, in the Pallas interpreter (``interpret=True``, the default).  It
has not been compiled for a TPU.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.neuron import LIFParams, LIFState
from .kernel import LANES, lif_update_f32, lif_update_fx32


def _to_tiles(x, n_pad, dtype):
    x = jnp.asarray(x, dtype)
    x = jnp.pad(x, (0, n_pad - x.shape[0]))
    return x.reshape(-1, LANES)


def lif_update(state: LIFState, g_in, params: LIFParams, v_in=None,
               force=None, interpret: bool = True):
    """Flat [n] fused update, float path.  Returns (LIFState, spikes bool[n])."""
    n = state.v.shape[0]
    n_pad = ((n + LANES - 1) // LANES) * LANES
    zeros_f = jnp.zeros(n, jnp.float32)
    zeros_i = jnp.zeros(n, jnp.int32)
    args = [_to_tiles(state.v, n_pad, jnp.float32),
            _to_tiles(state.g, n_pad, jnp.float32),
            _to_tiles(state.refrac, n_pad, jnp.int32),
            _to_tiles(g_in, n_pad, jnp.float32),
            _to_tiles(v_in if v_in is not None else zeros_f, n_pad,
                      jnp.float32),
            _to_tiles(force.astype(jnp.int32) if force is not None
                      else zeros_i, n_pad, jnp.int32)]
    v, g, refrac, spk = lif_update_f32(*args, params=params,
                                       interpret=interpret)
    st = LIFState(v=v.reshape(-1)[:n], g=g.reshape(-1)[:n],
                  refrac=refrac.reshape(-1)[:n])
    return st, (spk.reshape(-1)[:n] != 0)


def lif_update_fx(state: LIFState, g_in_units, params: LIFParams,
                  v_in_units=None, force=None, interpret: bool = True):
    """Flat [n] fused update, int32 fixed-point path."""
    n = state.v.shape[0]
    n_pad = ((n + LANES - 1) // LANES) * LANES
    zeros_i = jnp.zeros(n, jnp.int32)
    args = [_to_tiles(state.v, n_pad, jnp.int32),
            _to_tiles(state.g, n_pad, jnp.int32),
            _to_tiles(state.refrac, n_pad, jnp.int32),
            _to_tiles(g_in_units, n_pad, jnp.int32),
            _to_tiles(v_in_units if v_in_units is not None else zeros_i,
                      n_pad, jnp.int32),
            _to_tiles(force.astype(jnp.int32) if force is not None
                      else zeros_i, n_pad, jnp.int32)]
    v, g, refrac, spk = lif_update_fx32(*args, params=params,
                                        interpret=interpret)
    st = LIFState(v=v.reshape(-1)[:n], g=g.reshape(-1)[:n],
                  refrac=refrac.reshape(-1)[:n])
    return st, (spk.reshape(-1)[:n] != 0)
