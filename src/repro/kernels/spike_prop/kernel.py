"""Block-gated synaptic-delivery Pallas kernel (TPU adaptation of the
paper's event-driven spike propagation).

Loihi 2 delivers each spike event through per-core synaptic memory; cost is
proportional to spike activity.  A TPU has no per-event branching — the
native granularity of an "event" is a tile.  We therefore adapt the paper's
insight as *block-level* event-driven delivery:

  * synapses are grouped into dense (TGT_BLK x SRC_BLK) weight tiles, stored
    only for (target-block, source-block) pairs that contain synapses
    (blocked-ELL: each target block owns up to E tiles);
  * per step the kernel walks grid (target_blocks, E) and for each tile
    checks the *source-block spike count* — if the source block emitted no
    spikes this step, the whole tile's matvec is skipped via ``pl.when``
    (the MXU work and the HBM->VMEM weight-tile stream for gated tiles is
    saved on real hardware via the grid-level DMA skip);
  * live tiles do a dense [TGT_BLK, SRC_BLK] x [SRC_BLK] matvec on the MXU
    and accumulate into the target block's conductance drive.

Cost ∝ (number of live tiles) — the TPU-native rendering of "execution cost
proportional to spiking activity rather than synapse count".

BlockSpec geometry: weight tiles [1, 1, TGT_BLK, SRC_BLK] stream through
VMEM indexed by (tb, e); the spike vector is blocked [1, 1, SRC_BLK] by
the tile's source-block id via a scalar-prefetch index map (``blk_id`` in
SMEM).  Every per-neuron operand is laid out [rows, 1, 128] so a one-row
block's last two dims equal the array's — the TPU compiler refuses a
(1, 128) block over [rows, 128] — and the unfused kernel's per-block spike
counts ride in SMEM as a second scalar-prefetch operand.

Both kernels compile for the TPU (``interpret=False``, the engines' choice
when ``jax.default_backend() == "tpu"``; tests/test_tpu_compile.py
compiles them for a described v5e) and run in the Pallas interpreter
everywhere else, which is how the CPU test suite exercises them.

The fused variant (:func:`fused_deliver_lif_pallas`) goes one step
further and closes the paper's whole per-timestep loop inside VMEM:
after the last live tile of a target-row block has been accumulated, the
same kernel invocation applies the :mod:`repro.core.neuron` LIF step
(int32 Q19.12 Loihi-faithful path or float32) to that block and emits
the spike vector directly.  The delivered current lives only in a VMEM
scratch accumulator — it never round-trips through HBM between delivery
and integration, which is exactly the locality the paper credits for
Loihi 2's speed (spike delivery and neuron update share one local
memory).  The tile-skip decision is fused too: the per-block any-spike
mask (``repro.core.compaction.two_level_active``'s first level) is
re-derived from the VMEM-resident spike block instead of arriving as a
precomputed count array, so neither the delivered currents nor the block
mask ever leave VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.neuron import LIFState, lif_step, lif_step_fx

TGT_BLK = 128
SRC_BLK = 128


def _tile_matvec(w_ref, s):
    """[1, SRC_BLK] spike row x the [TGT_BLK, SRC_BLK] tile -> [1, TGT_BLK]
    drive row on the MXU.  HIGHEST precision keeps f32 weights exact on
    the chip (integer weights and 0/1 spikes then sum exactly, as in the
    XLA engines)."""
    return jax.lax.dot_general(
        s, w_ref[0, 0], (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _deliver_body(blk_id_ref, nspk_ref, spk_ref, w_ref, out_ref):
    """grid = (n_tgt_blocks, E); accumulate gated tile matvecs."""
    tb, e = pl.program_id(0), pl.program_id(1)

    @pl.when(e == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # per-source-block spike count, read from SMEM by the tile's block id
    @pl.when(nspk_ref[blk_id_ref[tb, e]] > 0)
    def _tile():
        out_ref[0] += _tile_matvec(w_ref, spk_ref[0])


# target blocks are independent; the E axis accumulates into the same
# output block and must stay sequential
_GRID_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def _rows3(x):
    """[m, 128] -> [m, 1, 128]: each row is its own block whose last two
    dims equal the array's, the layout Mosaic accepts for one-row blocks."""
    return x.reshape(x.shape[0], 1, x.shape[1])


def spike_deliver_pallas(blk_id, weights, spk_blocks, nspk_blocks,
                         interpret: bool = True):
    """Args:
      blk_id:      [n_tb, E] int32 source-block id per tile (pad rows allowed
                   — they point at an all-zero spike block).
      weights:     [n_tb, E, TGT_BLK, SRC_BLK] f32 dense tiles.
      spk_blocks:  [n_sb + 1, SRC_BLK] f32 spikes grouped by source block;
                   row n_sb is the zero pad block.
      nspk_blocks: [n_sb + 1] int32 per-source-block spike counts.
    Returns: [n_tb, TGT_BLK] f32 accumulated drive.
    """
    n_tb, E = blk_id.shape
    # scalar prefetch: blk_id and the spike counts go to SMEM; blk_id drives
    # the spike-block index map (data-dependent DMA scheduling) and the
    # counts gate each tile
    kernel = pl.pallas_call(
        _deliver_body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tb, E),
            in_specs=[
                pl.BlockSpec((1, 1, SRC_BLK),
                             lambda tb, e, blk, nspk: (blk[tb, e], 0, 0)),
                pl.BlockSpec((1, 1, TGT_BLK, SRC_BLK),
                             lambda tb, e, blk, nspk: (tb, e, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, TGT_BLK),
                                   lambda tb, e, blk, nspk: (tb, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_tb, 1, TGT_BLK), jnp.float32),
        compiler_params=_GRID_PARAMS,
        interpret=interpret,
    )
    out = kernel(blk_id, nspk_blocks, _rows3(spk_blocks), weights)
    return out.reshape(n_tb, TGT_BLK)


# --------------------------------------------------------------------------
# Fused delivery -> LIF: the whole timestep of a target-row block in VMEM
# --------------------------------------------------------------------------

def _accumulate_tile(spk_ref, w_ref, acc_ref):
    """Shared delivery preamble of the fused bodies: zero the VMEM
    accumulator on the first tile slot, then add the gated tile matvec.

    The live check re-derives the per-block any-spike mask (the first
    level of ``repro.core.compaction.two_level_active``) from the
    VMEM-resident spike block — equivalent to the unfused kernel's
    ``nspk > 0`` gate (spike lanes are exactly 0/1) but the mask is never
    materialized outside the kernel.
    """
    e = pl.program_id(1)

    @pl.when(e == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    s = spk_ref[0]                        # [1, SRC_BLK] f32 spike block

    @pl.when(jnp.max(s) > 0.0)
    def _tile():
        acc_ref[...] += _tile_matvec(w_ref, s)


def _fused_body(blk_id_ref, spk_ref, w_ref, v_ref, g_ref, ref_ref, *rest,
                params, fixed_point, use_gstim, use_vin, use_force):
    """grid = (n_tgt_blocks, E): accumulate, then integrate on the last
    slot.  The integration is not a re-implementation: it CALLS the very
    ``lif_step`` / ``lif_step_fx`` the unfused step body runs (pure jnp on
    the VMEM-resident block values), so bit-identity to the unfused
    composition is structural, not hand-synchronized.  Stimulus channels
    the caller's drive lacks are absent from the operand list entirely
    (``use_*`` flags), exactly mirroring ``apply_drive``'s ``None``
    short-circuits — and sparing their HBM->VMEM streams."""
    it = iter(rest[:use_gstim + use_vin + use_force])
    gstim_ref = next(it) if use_gstim else None
    vin_ref = next(it) if use_vin else None
    force_ref = next(it) if use_force else None
    v_out, g_out, refr_out, spk_out, acc_ref = \
        rest[use_gstim + use_vin + use_force:]

    _accumulate_tile(spk_ref, w_ref, acc_ref)
    e = pl.program_id(1)

    @pl.when(e == pl.num_programs(1) - 1)
    def _integrate():
        g_units = acc_ref[...]
        if use_gstim:
            g_units = g_units + gstim_ref[0]
        lif = LIFState(v=v_ref[0], g=g_ref[0], refrac=ref_ref[0])
        vin = vin_ref[0] if use_vin else None
        force = (force_ref[0] != 0) if use_force else None
        if fixed_point:
            # f32 accumulation -> integer weight units at the block
            # boundary, exactly apply_drive's conversion point
            st, spikes = lif_step_fx(
                lif, jnp.round(g_units).astype(jnp.int32), params, vin,
                force)
        else:
            st, spikes = lif_step(lif, g_units * params.w_scale, params,
                                  vin, force)
        v_out[0] = st.v
        g_out[0] = st.g
        refr_out[0] = st.refrac
        spk_out[0] = spikes.astype(jnp.int32)


def fused_deliver_lif_pallas(blk_id, weights, spk_blocks, v, g, refrac,
                             gstim=None, vin=None, force=None, *, params,
                             fixed_point: bool, interpret: bool = True):
    """One call = one whole timestep: spike->gather->accumulate->integrate->
    threshold per 128-neuron target-row block, entirely in VMEM.

    Args:
      blk_id / weights / spk_blocks: as :func:`spike_deliver_pallas` (no
        spike-count array — the block-live mask is derived in-kernel).
      v, g, refrac: LIF state as [n_tb, TGT_BLK] row blocks (f32 or
        Q19.12 int32 per ``fixed_point``; refrac always int32).
      gstim: optional [n_tb, TGT_BLK] f32 stimulus drive in weight units.
      vin:   optional [n_tb, TGT_BLK] membrane drive — mV f32 (float
        path) or pre-rounded w_scale units int32 (fixed-point path).
      force: optional [n_tb, TGT_BLK] int32 forced-spike mask.
      ``None`` channels are dropped from the operand list entirely (no
      zero arrays streamed), mirroring the unfused path's ``None``
      short-circuits.
    Returns: (v, g, refrac, spikes) row blocks; spikes int32 0/1.
    """
    n_tb, E = blk_id.shape
    sdt = jnp.int32 if fixed_point else jnp.float32
    body = functools.partial(
        _fused_body, params=params, fixed_point=fixed_point,
        use_gstim=gstim is not None, use_vin=vin is not None,
        use_force=force is not None)
    row = pl.BlockSpec((1, 1, TGT_BLK), lambda tb, e, blk: (tb, 0, 0))
    row_ops = [_rows3(x) for x in (v, g, refrac, gstim, vin, force)
               if x is not None]
    kernel = pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_tb, E),
            in_specs=[
                pl.BlockSpec((1, 1, SRC_BLK),
                             lambda tb, e, blk: (blk[tb, e], 0, 0)),
                pl.BlockSpec((1, 1, TGT_BLK, SRC_BLK),
                             lambda tb, e, blk: (tb, e, 0, 0)),
            ] + [row] * len(row_ops),
            out_specs=[row, row, row, row],
            # the delivered current's only home: a VMEM scratch accumulator
            scratch_shapes=[pltpu.VMEM((1, TGT_BLK), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n_tb, 1, TGT_BLK), dt)
            for dt in (sdt, sdt, jnp.int32, jnp.int32)],
        compiler_params=_GRID_PARAMS,
        interpret=interpret,
    )
    outs = kernel(blk_id, _rows3(spk_blocks), weights, *row_ops)
    return tuple(x.reshape(n_tb, TGT_BLK) for x in outs)
