"""The benchmark's connectome generator: one static shape for every seed."""

import numpy as np
import pytest

from bench import netgen

SPEC = {"n_neurons": 1003, "n_synapses": 40_000, "target_strata": 4,
        "out_degree_sigma": 1.1, "frac_inhibitory": 0.3, "frac_pm1": 0.45,
        "weight_geometric_p": 0.08, "weight_outlier_p": 2e-5,
        "weight_outlier_min": 300, "hub_attract_boost": 40.0}


@pytest.mark.parametrize("seed", [0, 2**31 - 1])
def test_shapes_and_strata_fixed(seed):
    net = netgen.generate(SPEC, seed)
    n = SPEC["n_neurons"]
    assert net.nnz == SPEC["n_synapses"]
    assert net.out_indptr[-1] == net.in_indptr[-1] == net.nnz
    off = netgen.strata_offsets(n, 4)
    per = np.diff(net.in_indptr[off])
    assert (per == net.nnz // 4).all()
    assert (np.diff(net.out_indptr) >= 1).all()
    src = np.repeat(np.arange(n), np.diff(net.out_indptr))
    assert not (src == net.out_indices).any()
    # Dale's law: one sign per source
    sign = np.sign(net.out_weights)
    first = sign[net.out_indptr[:-1]]
    assert (sign == np.repeat(first, np.diff(net.out_indptr))).all()


def test_target_major_view_is_the_same_graph():
    net = netgen.generate(SPEC, 3)
    n = SPEC["n_neurons"]
    src = np.repeat(np.arange(n), np.diff(net.out_indptr))
    tgt = np.repeat(np.arange(n), np.diff(net.in_indptr))
    a = np.lexsort((net.out_weights, src, net.out_indices))
    b = np.lexsort((net.in_weights, net.in_indices, tgt))
    assert np.array_equal(net.out_indices[a], tgt[b])
    assert np.array_equal(src[a], net.in_indices[b])
    assert np.array_equal(net.out_weights[a], net.in_weights[b])


def test_same_seed_same_graph_other_seed_other_graph():
    a, b, c = (netgen.generate(SPEC, s) for s in (5, 5, 6))
    assert np.array_equal(a.out_indices, b.out_indices)
    assert not np.array_equal(a.out_indices, c.out_indices)
