"""GPipe-style pipeline parallelism over a "stage" mesh axis.

Each device owns one stage's params; microbatches stream through the
stages via collective_permute (ppermute), M + S - 1 ticks for M
microbatches over S stages (bubble fraction (S-1)/(M+S-1)).

The schedule runs under shard_map on a real mesh or under vmap with an
axis name (tests).  It is the optional PP axis for the LM stack — the
production mesh uses DP x TP (+ pod DP); PP composes by replacing the
layer scan with stage-sharded sub-stacks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def pipeline_schedule(stage_fn, params_local, xs, *, axis: str,
                      n_stages: int):
    """Runs inside shard_map/vmap.  params_local: this stage's params;
    xs: [M, ...] microbatches (same on every stage; only stage 0 reads
    them).  Returns [M, ...] outputs (valid on the last stage, zeros
    elsewhere — callers psum or read the last stage's shard)."""
    S = n_stages
    M = xs.shape[0]
    stage = jax.lax.axis_index(axis)
    mb_shape = xs.shape[1:]

    # cyclic shift: S-1 -> 0 wraps harmlessly (stage 0 ignores its recv);
    # a full permutation is required by vmap's ppermute batching rule
    perm = [(i, (i + 1) % S) for i in range(S)]

    def tick(t, carry):
        recv, outs = carry
        ingest = jnp.where(t < M, jnp.minimum(t, M - 1), 0)
        x0 = xs[ingest]
        x = jnp.where(stage == 0, x0, recv)
        y = stage_fn(params_local, x)
        recv_next = jax.lax.ppermute(y, axis, perm)
        out_t = jnp.clip(t - (S - 1), 0, M - 1)
        emit = jnp.logical_and(stage == S - 1, t >= S - 1)
        outs = outs.at[out_t].set(jnp.where(emit, y, outs[out_t]))
        return recv_next, outs

    recv0 = jnp.zeros(mb_shape, xs.dtype)
    outs0 = jnp.zeros((M,) + mb_shape, xs.dtype)
    _, outs = jax.lax.fori_loop(0, M + S - 1, tick, (recv0, outs0))
    return outs


def pipeline_apply_emulated(stage_fn, stage_params, xs, n_stages: int):
    """vmap-emulated pipeline (single device): stage_params leaves
    [S, ...]; xs [M, ...].  Returns [M, ...] from the last stage."""
    axis = "stage"

    def per_stage(params_local):
        return pipeline_schedule(stage_fn, params_local, xs, axis=axis,
                                 n_stages=n_stages)

    outs = jax.vmap(per_stage, axis_name=axis)(stage_params)
    return outs[-1]            # last stage holds the real outputs


def pipeline_apply(stage_fn, stage_params, xs, mesh, n_stages: int,
                   axis: str = "stage"):
    """shard_map pipeline on a real mesh with a `stage` axis."""
    from jax.sharding import PartitionSpec as P

    def shard_fn(params, xs_all):
        params = jax.tree.map(lambda a: a[0], params)
        outs = pipeline_schedule(stage_fn, params, xs_all, axis=axis,
                                 n_stages=n_stages)
        # deliver outputs everywhere (tests read them host-side)
        stage = jax.lax.axis_index(axis)
        outs = jnp.where(stage == n_stages - 1, outs, 0)
        return jax.lax.psum(outs, axis)

    spec_p = jax.tree.map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=(spec_p, P()),
                       out_specs=P(), check_vma=False)
    return jax.jit(fn)(stage_params, xs)
