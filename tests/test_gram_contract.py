"""Gram-matrix contract: on a small bucketed dataset the GramDriver
output must be symmetric, match pairwise ``mgk_direct``, and be PSD
after standard jitter; its gradient blocks (run_with_grad) must match
central finite differences of the Gram entries, dense and sparse paths
agreeing with each other."""
import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from repro.core import KroneckerDelta, SquareExponential
from repro.core.reference import mgk_direct
from repro.data import bucket_graphs, make_drugbank_like_dataset
from repro.distributed import GramDriver

VK = KroneckerDelta(0.5, n_labels=8)
EK = SquareExponential(1.0, rank=12)


@pytest.fixture(scope="module")
def setup():
    graphs = [g for g in make_drugbank_like_dataset(16, seed=1)
              if 5 <= g.n_nodes <= 40][:8]
    assert len(graphs) == 8
    ds = bucket_graphs(graphs, max_buckets=2)
    mesh = Mesh(np.array(jax.devices()).reshape(1, 1), ("data", "model"))
    return graphs, ds, mesh


def _driver(ds, mesh, **kw):
    base = dict(vertex_kernel=VK, edge_kernel=EK, method="lowrank",
                pairs_per_block=16, normalize=False, tol=1e-10)
    base.update(kw)
    return GramDriver(ds, mesh, **base)


@pytest.fixture(scope="module")
def gram_and_grads(setup):
    _, ds, mesh = setup
    return _driver(ds, mesh).run_with_grad()


def test_gram_symmetric_and_matches_direct(setup, gram_and_grads):
    graphs, _, _ = setup
    K, _ = gram_and_grads
    assert K.shape == (len(graphs), len(graphs))
    assert not np.isnan(K).any()
    np.testing.assert_allclose(K, K.T, rtol=1e-5)
    for i, j in [(0, 0), (0, 3), (2, 5), (6, 7)]:
        ref = mgk_direct(graphs[i], graphs[j], VK, EK)
        assert K[i, j] == pytest.approx(ref, rel=2e-3)


def test_gram_psd_after_jitter(gram_and_grads):
    K, _ = gram_and_grads
    jitter = 1e-8 * np.trace(K) / K.shape[0]
    w = np.linalg.eigvalsh(K + jitter * np.eye(K.shape[0]))
    assert w.min() > -1e-6 * abs(w.max())


def test_grad_blocks_match_finite_differences(setup, gram_and_grads):
    _, ds, mesh = setup
    K, G = gram_and_grads
    assert set(G) == {"vertex.h", "edge.alpha"}
    for g in G.values():
        np.testing.assert_allclose(g, g.T, rtol=1e-4, atol=1e-8)
    h = 2e-3
    cases = [
        ("edge.alpha",
         lambda s: _driver(ds, mesh,
                           edge_kernel=SquareExponential(1.0 + s,
                                                         rank=12))),
        ("vertex.h",
         lambda s: _driver(ds, mesh,
                           vertex_kernel=KroneckerDelta(0.5 + s,
                                                        n_labels=8))),
    ]
    for key, make in cases:
        Kp = make(+h).run()
        Km = make(-h).run()
        fd = (Kp - Km) / (2 * h)
        np.testing.assert_allclose(G[key], fd, rtol=2e-3, atol=2e-5)


@pytest.mark.parametrize("mode", ["auto", "mxu"])
def test_sparse_grad_blocks_match_dense(setup, gram_and_grads, mode):
    """The pack-cached sparse gradient path must reproduce the
    dense-path gradient Gram: in the elementwise contraction "auto"
    picks (traced theta through the in-kernel vector) and in the MXU one
    (values_w/values_grad baked once per graph, trust_pack_weights)."""
    _, ds, mesh = setup
    K, G = gram_and_grads
    Ks, Gs = _driver(ds, mesh, method="pallas_sparse",
                     sparse_mode=mode).run_with_grad()
    np.testing.assert_allclose(Ks, K, rtol=2e-3, atol=1e-7)
    for key in G:
        np.testing.assert_allclose(Gs[key], G[key], rtol=5e-3, atol=2e-5)


def test_grad_blocks_survive_the_chunk_store(setup, tmp_path_factory):
    """Gradient blocks ride the fault-tolerance path too: persisted per
    block, reassembled identically on restart."""
    from repro.distributed.checkpoint import ChunkStore
    _, ds, mesh = setup
    root = str(tmp_path_factory.mktemp("gram_grad_store"))
    drv = _driver(ds, mesh, store=ChunkStore(root))
    K1, G1 = drv.run_with_grad()
    # a fresh driver over the same store recomputes nothing
    drv2 = _driver(ds, mesh, store=ChunkStore(root))
    K2, G2 = drv2.run_with_grad()
    np.testing.assert_array_equal(K1, K2)
    for key in G1:
        np.testing.assert_array_equal(G1[key], G2[key])
