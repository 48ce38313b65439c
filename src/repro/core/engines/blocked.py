"""Blocked engine: block-gated Pallas spike delivery (TPU-native event path).

Wires the :mod:`repro.kernels.spike_prop` blocked-ELL kernel into the
simulation loop as a first-class engine.  Synapses are grouped into dense
(128 x 128) weight tiles stored only for nonempty (target-block,
source-block) pairs; per step the kernel skips every tile whose source
block emitted no spikes, so cost ∝ live tiles — the tile-granular
rendering of "execution cost proportional to spiking activity rather
than synapse count".

The tile store is built on host once per ``build`` (i.e. once per
``simulate()`` call, or once per benchmark when the caller reuses the
state) and lives on device thereafter; the per-step ``deliver`` only
moves the spike vector.  ``build`` picks the mode from the backend: on a
TPU the kernel runs compiled by Mosaic (scalar-prefetch DMA gating;
``chip_smoke.py`` runs it on a v5e), on any other backend it runs in the
Pallas interpreter, which is how the CPU tests exercise it — slowly, as
the interpreter unrolls every stored tile.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..connectome import Connectome
from .base import quantized_in_weights, register, register_state, static_field


@register_state
@dataclasses.dataclass(frozen=True)
class BlockedState:
    blk_id: jax.Array                 # [n_tb, E] i32 source-block per tile
    weights: jax.Array                # [n_tb, E, TGT_BLK, SRC_BLK] f32
    n: int = static_field(default=0)
    n_sb: int = static_field(default=0)
    interpret: bool = static_field(default=True)
    occupancy: float = static_field(default=0.0)
    tiles_stored: int = static_field(default=0)


@register
class BlockedEngine:
    name = "blocked"

    def build(self, c: Connectome, cfg) -> BlockedState:
        from repro.kernels.spike_prop.ops import build_blocked
        w = quantized_in_weights(c, cfg)
        bs = build_blocked(c, quantized=w if cfg.quantize_bits else None)
        return BlockedState(
            blk_id=jnp.asarray(bs.blk_id), weights=jnp.asarray(bs.weights),
            n=bs.n, n_sb=bs.n_sb,
            interpret=jax.default_backend() != "tpu",
            occupancy=bs.occupancy, tiles_stored=bs.tiles_stored)

    def deliver(self, state: BlockedState, spikes: jax.Array, cfg):
        from repro.kernels.spike_prop.kernel import spike_deliver_pallas
        from repro.kernels.spike_prop.ops import pad_spike_blocks
        spk_pad, nspk = pad_spike_blocks(spikes, state.n, state.n_sb)
        out = spike_deliver_pallas(state.blk_id, state.weights, spk_pad, nspk,
                                   interpret=state.interpret)
        return out.reshape(-1)[:state.n], jnp.int32(0)
