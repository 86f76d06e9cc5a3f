"""End-to-end marginalized graph kernel: against two independent oracles,
plus the paper's structural properties (symmetry, permutation invariance,
PSD Gram, small-stopping-probability convergence, reordering invariance).
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import (KroneckerDelta, SquareExponential, batch_from_graphs,
                        mgk_pairs, pbr_order, rcm_order)
from repro.core.mgk import mgk_pairs_sparse
from repro.core.reference import mgk_direct, mgk_walk_sum
from repro.data import make_drugbank_like_dataset, make_synthetic_dataset
from repro.kernels.ops import row_panel_packs_for_batch

VK = KroneckerDelta(0.5, n_labels=8)
EK = SquareExponential(1.0, rank=12)


def _graphs(n=6, nodes=14, seed=0, stop=0.1):
    return make_synthetic_dataset("nws", n_graphs=n, n_nodes=nodes,
                                  seed=seed, stop_prob=stop)


@pytest.mark.parametrize("method", ["full", "elementwise", "lowrank",
                                    "pallas"])
def test_matches_direct_oracle(method):
    gs = _graphs(4)
    g1 = batch_from_graphs(gs[:2], pad_to=16)
    g2 = batch_from_graphs(gs[2:], pad_to=16)
    res = mgk_pairs(g1, g2, VK, EK, method=method, tol=1e-12)
    ref = [mgk_direct(gs[i], gs[2 + i], VK, EK) for i in range(2)]
    np.testing.assert_allclose(np.asarray(res.values), ref, rtol=1e-4)
    assert bool(res.converged.all())


def test_matches_walk_sum_definition():
    """Validates the linear-algebra reformulation (paper Appendix A)
    against the kernel's random-walk DEFINITION."""
    gs = _graphs(2, nodes=10, stop=0.3)
    g1 = batch_from_graphs(gs[:1], pad_to=16)
    g2 = batch_from_graphs(gs[1:], pad_to=16)
    res = mgk_pairs(g1, g2, VK, EK, method="full", tol=1e-12)
    ws = mgk_walk_sum(gs[0], gs[1], VK, EK, max_len=500)
    np.testing.assert_allclose(float(res.values[0]), ws, rtol=1e-4)


def test_symmetry():
    gs = _graphs(4)
    a = batch_from_graphs(gs[:2], pad_to=16)
    b = batch_from_graphs(gs[2:], pad_to=16)
    k_ab = mgk_pairs(a, b, VK, EK, tol=1e-12).values
    k_ba = mgk_pairs(b, a, VK, EK, tol=1e-12).values
    np.testing.assert_allclose(np.asarray(k_ab), np.asarray(k_ba),
                               rtol=1e-5)


def test_permutation_invariance(rng):
    gs = _graphs(2, nodes=12)
    perm = rng.permutation(12)
    gp = gs[0].permuted(perm)
    a = batch_from_graphs([gs[0], gp], pad_to=16)
    b = batch_from_graphs([gs[1], gs[1]], pad_to=16)
    res = mgk_pairs(a, b, VK, EK, tol=1e-12)
    np.testing.assert_allclose(float(res.values[0]), float(res.values[1]),
                               rtol=1e-4)


@pytest.mark.parametrize("order_fn", [rcm_order, pbr_order])
def test_reordering_invariance(order_fn):
    """Reordering is a performance transform — kernel values must not
    change (paper Sec. IV-A)."""
    gs = make_drugbank_like_dataset(6, seed=3)
    gs = [g for g in gs if g.n_nodes >= 8][:2]
    g = gs[0]
    p = order_fn(g.adjacency)
    a = batch_from_graphs([g, g.permuted(p)], pad_to=None)
    b = batch_from_graphs([gs[1], gs[1]], pad_to=None)
    res = mgk_pairs(a, b, VK, EK, tol=1e-12)
    np.testing.assert_allclose(float(res.values[0]), float(res.values[1]),
                               rtol=1e-4)


def test_small_stopping_probability_converges():
    """The paper highlights convergence at stopping probabilities as small
    as 0.0005 where CPU baselines fail."""
    gs = make_synthetic_dataset("nws", n_graphs=2, n_nodes=16, seed=1,
                                stop_prob=0.0005)
    a = batch_from_graphs(gs[:1])
    b = batch_from_graphs(gs[1:])
    res = mgk_pairs(a, b, VK, EK, tol=1e-10, max_iter=2000)
    assert bool(res.converged.all())
    ref = mgk_direct(gs[0], gs[1], VK, EK)
    np.testing.assert_allclose(float(res.values[0]), ref, rtol=1e-3)


def test_gram_matrix_psd():
    gs = _graphs(8, nodes=12)
    n = len(gs)
    K = np.zeros((n, n))
    batch_a, batch_b, idx = [], [], []
    for i in range(n):
        for j in range(i, n):
            batch_a.append(gs[i])
            batch_b.append(gs[j])
            idx.append((i, j))
    a = batch_from_graphs(batch_a, pad_to=16)
    b = batch_from_graphs(batch_b, pad_to=16)
    vals = np.asarray(mgk_pairs(a, b, VK, EK, tol=1e-10).values)
    for (i, j), v in zip(idx, vals):
        K[i, j] = K[j, i] = v
    w = np.linalg.eigvalsh(K)
    assert w.min() > -1e-6 * abs(w.max())


def test_sparse_path_matches_dense():
    gs = make_drugbank_like_dataset(8, seed=5)
    gs = [g for g in gs if g.n_nodes >= 6][:4]
    a = batch_from_graphs(gs[:2], pad_to=64)
    b = batch_from_graphs(gs[2:], pad_to=64)
    packs_a = row_panel_packs_for_batch(a)
    packs_b = row_panel_packs_for_batch(b)
    rs = mgk_pairs_sparse(a, b, packs_a, packs_b, VK, EK, tol=1e-12)
    rd = mgk_pairs(a, b, VK, EK, method="full", tol=1e-12)
    np.testing.assert_allclose(np.asarray(rs.values),
                               np.asarray(rd.values), rtol=1e-4)


def test_nodal_similarity_shape():
    gs = _graphs(2, nodes=10)
    a = batch_from_graphs(gs[:1], pad_to=16)
    b = batch_from_graphs(gs[1:], pad_to=16)
    res = mgk_pairs(a, b, VK, EK, return_nodal=True)
    assert res.nodal.shape == (1, 16, 16)
    # kernel value equals p^T-weighted nodal sum
    px = np.asarray(a.start_prob[0])[:, None] * \
        np.asarray(b.start_prob[0])[None, :]
    np.testing.assert_allclose(float((px * np.asarray(res.nodal[0])).sum()),
                               float(res.values[0]), rtol=1e-5)
