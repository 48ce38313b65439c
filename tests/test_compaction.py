"""Hierarchical active-set compaction + bounded ragged gather: the shared
sparse-path primitives (repro.core.compaction) against numpy references."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.compaction import (BLOCK, SLOT_CHUNKS, active_fanout_total,
                                   deliver_ragged, derived_block_capacity,
                                   n_blocks, ragged_slots, slot_chunk,
                                   slot_owner, two_level_active)


def np_two_level(spikes: np.ndarray, cap: int, bcap: int,
                 block: int = BLOCK) -> np.ndarray:
    """Reference semantics: first ``bcap`` active blocks by id, first
    ``cap`` active neurons by id within them, fill = n."""
    n = len(spikes)
    ids = np.flatnonzero(spikes)
    kept_blocks = np.unique(ids // block)[:bcap]
    kept = ids[np.isin(ids // block, kept_blocks)][:cap]
    out = np.full(cap, n, np.int64)
    out[:len(kept)] = kept
    return out


@pytest.mark.parametrize("n", [100, 128, 1000, 5000])
@pytest.mark.parametrize("density", [0.0, 0.002, 0.05])
def test_two_level_matches_flat_where_with_ample_capacity(n, density):
    rng = np.random.default_rng(n + int(density * 1000))
    spikes = rng.random(n) < density
    cap = max(8, int(spikes.sum()) + 4)
    bcap = derived_block_capacity(n, cap)
    got = np.asarray(two_level_active(jnp.asarray(spikes), cap, bcap))
    want = np.asarray(jnp.where(jnp.asarray(spikes), size=cap,
                                fill_value=n)[0])
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, np_two_level(spikes, cap, bcap))


@pytest.mark.parametrize("cap,bcap", [(4, 64), (64, 2), (3, 1), (6, 3)])
def test_two_level_overflow_keeps_hierarchical_prefix(cap, bcap):
    """Under overflow the kept set is the documented deterministic prefix —
    what the exact drop accounting and the numpy references rely on."""
    n = 2000
    rng = np.random.default_rng(7)
    spikes = rng.random(n) < 0.02   # ~40 spikes over ~16 blocks
    got = np.asarray(two_level_active(jnp.asarray(spikes), cap, bcap))
    np.testing.assert_array_equal(got, np_two_level(spikes, cap, bcap))


def test_two_level_empty_and_full():
    n = 300
    cap, bcap = 8, derived_block_capacity(n, 8)
    got = np.asarray(two_level_active(jnp.zeros(n, bool), cap, bcap))
    np.testing.assert_array_equal(got, np.full(cap, n))
    got = np.asarray(two_level_active(jnp.ones(n, bool), cap, bcap))
    np.testing.assert_array_equal(got, np_two_level(np.ones(n, bool), cap,
                                                    bcap))


def test_slot_owner_equals_searchsorted():
    rng = np.random.default_rng(0)
    lens = rng.integers(0, 30, 17)
    seg_end = np.cumsum(lens).astype(np.int32)
    budget = 200
    got = np.asarray(slot_owner(jnp.asarray(seg_end), budget))
    want = np.searchsorted(seg_end, np.arange(budget), side="right")
    np.testing.assert_array_equal(got, want)
    # a window [lo, lo + 48) of the same assignment
    got = np.asarray(slot_owner(jnp.asarray(seg_end), 48, lo=100))
    np.testing.assert_array_equal(got, want[100:148])


def test_ragged_slots_matches_numpy_reference():
    rng = np.random.default_rng(3)
    n, budget = 40, 64
    lens = rng.integers(0, 9, n)
    indptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    nnz = int(indptr[-1])
    ids = np.array([5, 0, 17, n, 39, n, n, 12], np.int32)  # n = invalid
    syn_ix, ok, total = ragged_slots(
        jnp.asarray(ids), jnp.asarray(indptr), budget,
        invalid_from=n, gather_size=nnz)
    flat = np.concatenate([np.arange(indptr[i], indptr[i + 1])
                           for i in ids if i < n] or [np.array([], int)])
    assert int(total) == len(flat)
    keep = flat[:budget]
    got = np.asarray(syn_ix)[np.asarray(ok)]
    np.testing.assert_array_equal(got, keep)
    # windows [lo, lo + 24) of the budget, side by side, are the whole pass
    windows = [ragged_slots(jnp.asarray(ids), jnp.asarray(indptr), budget,
                            invalid_from=n, gather_size=nnz, lo=lo,
                            window=24) for lo in range(0, budget, 24)]
    for part, want in zip(range(2), (syn_ix, ok)):
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(w[part]) for w in windows])[:budget],
            np.asarray(want))
    # starved budget: prefix kept, total still reports the full request
    syn_ix, ok, total = ragged_slots(
        jnp.asarray(ids), jnp.asarray(indptr), 7,
        invalid_from=n, gather_size=nnz)
    assert int(total) == len(flat)
    np.testing.assert_array_equal(np.asarray(syn_ix)[np.asarray(ok)],
                                  flat[:7])


def test_active_fanout_total():
    rng = np.random.default_rng(1)
    lens = rng.integers(0, 50, 200)
    indptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    spikes = rng.random(200) < 0.3
    got = int(active_fanout_total(jnp.asarray(spikes), jnp.asarray(indptr)))
    assert got == int(lens[spikes].sum())


def test_block_helpers():
    assert n_blocks(256) == 2 and n_blocks(257) == 3
    assert derived_block_capacity(60_000, 64) == 64       # cap-limited
    assert derived_block_capacity(400, 64) == n_blocks(400)  # block-limited
    assert derived_block_capacity(1, 1) == 1


def test_slot_chunk_sizes():
    """Whole 128-lane rows, at most SLOT_CHUNKS chunks per budget."""
    assert slot_chunk(65_536) == 4_096
    assert slot_chunk(597_628) == 37_376
    assert slot_chunk(149_407) == 9_344
    for budget in (1, 127, 300, 5_000, 65_536, 597_628):
        c = slot_chunk(budget)
        assert c % 128 == 0 and -(-budget // c) <= SLOT_CHUNKS


def _store(seed: int, n_src: int, n_out: int):
    """A source-major synapse store with integer weights and about a fifth
    of the sources without fan-out."""
    rng = np.random.default_rng(seed)
    fo = rng.integers(1, 40, n_src)
    fo[rng.random(n_src) < 0.2] = 0
    indptr = np.concatenate([[0], np.cumsum(fo)]).astype(np.int32)
    tgt = rng.integers(0, n_out, indptr[-1]).astype(np.int32)
    w = rng.integers(-60, 61, indptr[-1]).astype(np.float32)
    return indptr, tgt, w


def _one_pass(ids, indptr, tgt, w, budget, invalid_from, n_out):
    """One full-budget pass: ``ragged_slots`` over the whole budget, then
    the gather and ``segment_sum`` of every slot."""
    syn_ix, ok, total = ragged_slots(ids, indptr, budget,
                                     invalid_from=invalid_from,
                                     gather_size=tgt.shape[0])
    contrib = jnp.where(ok, w[syn_ix], 0.0)
    t = jnp.where(ok, tgt[syn_ix], n_out)
    g = jax.ops.segment_sum(contrib, t, num_segments=n_out + 1)[:n_out]
    return g, total, jnp.sum(ok)


N_SRC, N_OUT = 300, 97
INDPTR, TGT, W = _store(11, N_SRC, N_OUT)
FO = np.diff(INDPTR)


def _ids(chosen, k=64):
    out = np.full(k, N_SRC, np.int32)
    out[:len(chosen)] = np.sort(chosen)
    return out


def _lanes(rng):
    """Lanes with 0, 1 and about 8 trips of a 1,000-slot budget."""
    return np.stack([_ids([]), _ids([3, 40]),
                     _ids(rng.choice(N_SRC, 60, replace=False))]), 1_000


def _case(name):
    rng = np.random.default_rng(sum(map(ord, name)))
    if name == "no_active":
        return _ids([]), 4_096
    if name == "few":
        return _ids([7, 150, 299]), 4_096
    if name == "budget_full":
        ids = _ids(rng.choice(N_SRC, 40, replace=False))
        return ids, int(FO[ids[ids < N_SRC]].sum())
    if name == "overflow":
        return _ids(rng.choice(N_SRC, 60, replace=False)), 300
    if name == "budget_not_chunk_multiple":
        return _ids(rng.choice(N_SRC, 64, replace=False)), 5_000
    if name == "zero_fanout_inside":
        zero = np.flatnonzero(FO == 0)[:6]
        some = rng.choice(np.flatnonzero(FO > 0), 10, replace=False)
        return _ids(np.concatenate([zero, some])), 256
    return _lanes(rng)


@pytest.mark.parametrize("name", [
    "no_active", "few", "budget_full", "overflow",
    "budget_not_chunk_multiple", "zero_fanout_inside", "vmap_lanes"])
def test_deliver_ragged_equals_one_full_budget_pass(name):
    """The chunked slot loop delivers exactly what one full-budget pass
    does (g bit for bit, the requested total, the delivered count) and
    runs ceil(min(total, budget) / C) trips."""
    ids, budget = _case(name)
    store = (jnp.asarray(INDPTR), jnp.asarray(TGT), jnp.asarray(W))

    def chunked(i):
        return deliver_ragged(i, *store, budget, invalid_from=N_SRC,
                              n_out=N_OUT)

    def reference(i):
        return _one_pass(i, *store, budget, N_SRC, N_OUT)

    if ids.ndim == 2:
        chunked, reference = jax.vmap(chunked), jax.vmap(reference)
    g, total, chunks = jax.jit(chunked)(jnp.asarray(ids))
    g_ref, total_ref, delivered = jax.jit(reference)(jnp.asarray(ids))
    np.testing.assert_array_equal(np.asarray(g), np.asarray(g_ref))
    np.testing.assert_array_equal(np.asarray(total), np.asarray(total_ref))
    np.testing.assert_array_equal(np.minimum(np.asarray(total), budget),
                                  np.asarray(delivered))
    window = slot_chunk(budget)
    np.testing.assert_array_equal(
        np.asarray(chunks), -(-np.minimum(np.asarray(total), budget)
                              // window))
    if name == "vmap_lanes":     # the lanes' trip counts differ
        assert len(set(np.asarray(chunks).tolist())) == 3
    if name in ("overflow", "budget_not_chunk_multiple"):
        assert int(np.max(chunks)) > 1 and budget % window
    if name == "overflow":
        assert int(total) > budget


def test_deliver_events_p4_emulate_equals_one_full_budget_pass():
    """The event scheme's delivery on 4 partitions, vmapped over a named
    partition axis as the emulated run does: each partition's g, drops and
    trip count match one full-budget pass over its own store."""
    from repro.core.exchange.event import deliver_events
    P_, U = 4, 75
    n_glob = P_ * U
    stores = [_store(20 + p, n_glob, U) for p in range(P_)]
    nnz = max(len(s[1]) for s in stores)
    indptr = np.stack([s[0] for s in stores])
    tgt = np.stack([np.pad(s[1], (0, nnz - len(s[1]))) for s in stores])
    w = np.stack([np.pad(s[2], (0, nnz - len(s[2]))) for s in stores])
    rng = np.random.default_rng(4)
    events = np.full(P_ * 32, n_glob, np.int32)
    events[:50] = np.sort(rng.choice(n_glob, 50, replace=False))
    budget = 700

    run = jax.vmap(lambda ip, t, ww: deliver_events(
        jnp.asarray(events), ip, t, ww, U, n_glob, budget),
        axis_name="p")
    g, dropped, chunks = run(jnp.asarray(indptr), jnp.asarray(tgt),
                             jnp.asarray(w))
    for p in range(P_):
        g_ref, total, _ = _one_pass(jnp.asarray(events),
                                    jnp.asarray(indptr[p]),
                                    jnp.asarray(tgt[p]), jnp.asarray(w[p]),
                                    budget, n_glob, U)
        np.testing.assert_array_equal(np.asarray(g[p]), np.asarray(g_ref))
        assert int(dropped[p]) == max(int(total) - budget, 0)
        assert int(chunks[p]) == -(-min(int(total), budget)
                                   // slot_chunk(budget))
