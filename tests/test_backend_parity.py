"""Cross-backend parity: every XMV backend — dense Pallas, block-sparse
(legacy per-pair loop AND batched grid), elementwise, lowrank — must apply
the same operator on random masked batches; classic and pipelined PCG must
produce the same iterates; and the batched block-sparse bucket matvec must
be exactly ONE pallas_call (the tentpole claim of PR 1, checked on the
jaxpr)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.base_kernels import KroneckerDelta, SquareExponential
from repro.core.graph import batch_from_graphs
from repro.core.mgk import build_product_system, mgk_pairs, mgk_pairs_sparse
from repro.core.pcg import pcg_solve
from repro.core.xmv import xmv_elementwise, xmv_full, xmv_lowrank
from repro.data import make_drugbank_like_dataset
from repro.kernels.ops import packs_for_batch, xmv_block_sparse_unrolled
from repro.kernels.xmv_block_sparse import xmv_block_sparse_batched
from repro.kernels.xmv_block_sparse import from_tiles, to_tiles
from repro.kernels.xmv_dense import DENSE_TILE
from repro.kernels.xmv_dense import xmv_dense_batched as _xmv_dense_batched

VK = KroneckerDelta(0.5, n_labels=8)
EK = SquareExponential(1.0, rank=12)
TOL = dict(rtol=1e-5, atol=1e-5)


def xmv_dense_batched(A, E, Ap, Ep, P, ek, diag=None):
    """Node-major view of the tile-major dense kernel."""
    t = DENSE_TILE
    return from_tiles(_xmv_dense_batched(
        A, E, Ap, Ep, to_tiles(P, t), ek,
        diag=None if diag is None else to_tiles(diag, t)))


@pytest.fixture(scope="module")
def masked_batch():
    """Two aligned batches of real-ish sparse graphs + their tile packs."""
    gs = make_drugbank_like_dataset(16, seed=11)
    gs = [g for g in gs if 6 <= g.n_nodes <= 48][:8]
    assert len(gs) == 8
    g1 = batch_from_graphs(gs[:4], pad_to=48)
    g2 = batch_from_graphs(gs[4:], pad_to=48)
    return g1, g2, packs_for_batch(g1), packs_for_batch(g2)


def _random_p(g1, g2, seed=0):
    rng = np.random.default_rng(seed)
    B, n = g1.adjacency.shape[:2]
    m = g2.adjacency.shape[1]
    return jnp.asarray(rng.random((B, n, m)).astype(np.float32))


def test_all_backends_agree(masked_batch):
    """dense pallas / block-sparse (old loop + new batched grid) /
    elementwise / lowrank vs the full-materialization oracle."""
    g1, g2, p1, p2 = masked_batch
    P = _random_p(g1, g2)
    args = (g1.adjacency, g1.edge_labels, g2.adjacency, g2.edge_labels, P)

    y_full = jax.vmap(
        lambda a, e, ap, ep, p: xmv_full(a, e, ap, ep, p, EK))(*args)
    y_elem = jax.vmap(
        lambda a, e, ap, ep, p: xmv_elementwise(a, e, ap, ep, p, EK))(*args)
    y_lr = jax.vmap(
        lambda a, e, ap, ep, p: xmv_lowrank(a, e, ap, ep, p, EK))(*args)
    y_dense = xmv_dense_batched(*args, EK)
    y_sp_old = xmv_block_sparse_unrolled(p1, p2, P, EK)
    y_sp_new = xmv_block_sparse_batched(p1, p2, P, EK)

    ref = np.asarray(y_full)
    for name, y in [("elementwise", y_elem), ("lowrank", y_lr),
                    ("pallas_dense", y_dense), ("sparse_unrolled", y_sp_old),
                    ("sparse_batched", y_sp_new)]:
        np.testing.assert_allclose(np.asarray(y), ref, err_msg=name, **TOL)


def test_elementwise_non_divisible_chunk(masked_batch):
    """chunk is clamped, not an error, when it doesn't divide n."""
    g1, g2, _, _ = masked_batch
    P = _random_p(g1, g2)
    a, e = g1.adjacency[0], g1.edge_labels[0]
    ap, ep = g2.adjacency[0], g2.edge_labels[0]
    y_ref = xmv_full(a, e, ap, ep, P[0], EK)
    y = xmv_elementwise(a, e, ap, ep, P[0], EK, chunk=7)  # 7 ∤ 48
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), **TOL)


def _count_primitive(jaxpr, name):
    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            count += 1
        for v in eqn.params.values():
            if isinstance(v, jax.extend.core.ClosedJaxpr):
                count += _count_primitive(v.jaxpr, name)
            elif isinstance(v, jax.extend.core.Jaxpr):
                count += _count_primitive(v, name)
    return count


def test_batched_sparse_is_single_launch(masked_batch):
    """The tentpole: one bucket matvec == ONE pallas_call, however many
    pairs are in the bucket (vs B calls in the legacy loop)."""
    g1, g2, p1, p2 = masked_batch
    P = _random_p(g1, g2)

    def batched(P):
        return xmv_block_sparse_batched(p1, p2, P, EK)

    def unrolled(P):
        return xmv_block_sparse_unrolled(p1, p2, P, EK)

    B = P.shape[0]
    assert B >= 4
    n_batched = _count_primitive(jax.make_jaxpr(batched)(P).jaxpr,
                                 "pallas_call")
    n_unrolled = _count_primitive(jax.make_jaxpr(unrolled)(P).jaxpr,
                                  "pallas_call")
    assert n_batched == 1, f"expected 1 pallas_call, traced {n_batched}"
    assert n_unrolled == B


def test_fused_epilogue_matches_unfused(masked_batch):
    """In-kernel diag*p - y must be bitwise-close to the two-step
    reference on both the dense and block-sparse paths."""
    g1, g2, p1, p2 = masked_batch
    P = _random_p(g1, g2)
    rng = np.random.default_rng(1)
    diag = jnp.asarray(
        rng.random(P.shape).astype(np.float32) + 1.0)

    y_sp = xmv_block_sparse_batched(p1, p2, P, EK)
    ref_sp = np.asarray(diag) * np.asarray(P) - np.asarray(y_sp)
    fused_sp = xmv_block_sparse_batched(p1, p2, P, EK, diag=diag)
    np.testing.assert_allclose(np.asarray(fused_sp), ref_sp, **TOL)

    args = (g1.adjacency, g1.edge_labels, g2.adjacency, g2.edge_labels, P)
    y_d = xmv_dense_batched(*args, EK)
    ref_d = np.asarray(diag) * np.asarray(P) - np.asarray(y_d)
    fused_d = xmv_dense_batched(*args, EK, diag=diag)
    np.testing.assert_allclose(np.asarray(fused_d), ref_d, **TOL)


def test_pipelined_pcg_matches_classic_iterates(rng):
    B, N = 4, 32
    a = rng.random((B, N, N)).astype(np.float32)
    spd = np.einsum("bij,bkj->bik", a, a) + \
        N * np.eye(N, dtype=np.float32)[None]
    b = rng.random((B, N)).astype(np.float32)
    mv = lambda p: jnp.einsum("bij,bj->bi", spd, p)  # noqa: E731
    diag = jnp.asarray(np.einsum("bii->bi", spd))
    rc = pcg_solve(mv, jnp.asarray(b), diag, tol=1e-9, max_iter=500)
    rp = pcg_solve(mv, jnp.asarray(b), diag, tol=1e-9, max_iter=500,
                   variant="pipelined")
    assert bool(rc.converged.all()) and bool(rp.converged.all())
    # same convergence trajectory: iteration counts within +-1
    assert int(np.abs(np.asarray(rc.iterations)
                      - np.asarray(rp.iterations)).max()) <= 1
    np.testing.assert_allclose(np.asarray(rc.x), np.asarray(rp.x),
                               rtol=1e-3, atol=1e-5)

    # fixed-iteration contract: both run the exact same trip count
    fc = pcg_solve(mv, jnp.asarray(b), diag, tol=1e-12, fixed_iters=20)
    fp = pcg_solve(mv, jnp.asarray(b), diag, tol=1e-12, fixed_iters=20,
                   variant="pipelined")
    np.testing.assert_allclose(np.asarray(fc.x), np.asarray(fp.x),
                               rtol=1e-3, atol=1e-5)


def test_mgk_pipelined_matches_classic(masked_batch):
    g1, g2, p1, p2 = masked_batch
    rc = mgk_pairs(g1, g2, VK, EK, method="pallas", tol=1e-10)
    rp = mgk_pairs(g1, g2, VK, EK, method="pallas", tol=1e-10,
                   pcg_variant="pipelined")
    np.testing.assert_allclose(np.asarray(rc.values), np.asarray(rp.values),
                               rtol=1e-5)
    assert int(np.abs(np.asarray(rc.iterations)
                      - np.asarray(rp.iterations)).max()) <= 1

    rs_c = mgk_pairs_sparse(g1, g2, p1, p2, VK, EK, tol=1e-10)
    rs_p = mgk_pairs_sparse(g1, g2, p1, p2, VK, EK, tol=1e-10,
                            pcg_variant="pipelined")
    np.testing.assert_allclose(np.asarray(rs_c.values),
                               np.asarray(rs_p.values), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(rs_c.values),
                               np.asarray(rc.values), rtol=1e-4)


def test_mgk_sparse_fixed_iters_plumbed(masked_batch):
    """fixed_iters used to be silently ignored by mgk_pairs_sparse."""
    g1, g2, p1, p2 = masked_batch
    free = mgk_pairs_sparse(g1, g2, p1, p2, VK, EK, tol=1e-10)
    k = int(np.asarray(free.iterations).max())
    fixed = mgk_pairs_sparse(g1, g2, p1, p2, VK, EK, tol=1e-10,
                             fixed_iters=k)
    np.testing.assert_allclose(np.asarray(fixed.values),
                               np.asarray(free.values), rtol=1e-6)
    # a truncated run must actually truncate (proves the plumbing)
    short = mgk_pairs_sparse(g1, g2, p1, p2, VK, EK, tol=1e-30,
                             fixed_iters=3)
    assert int(np.asarray(short.iterations).max()) == 3


def test_fused_is_default_cg_operator(masked_batch):
    """The CG hot path for method='pallas' and the sparse path must carry
    the diagonal term in-kernel: the traced solve contains NO standalone
    diag*p multiply-subtract on the [B, n*m] vector outside the kernel.
    Cheap proxy: the matvec jaxpr's only computation at product-vector
    width is the pallas_call itself."""
    g1, g2, p1, p2 = masked_batch
    sys_ = build_product_system(g1, g2, VK)
    from repro.core.mgk import _make_matvec
    mv = _make_matvec(g1, g2, sys_, EK, "pallas", 8)
    B = g1.adjacency.shape[0]
    nm = g1.adjacency.shape[1] * g2.adjacency.shape[1]
    p = jnp.ones((B, nm), jnp.float32)
    jaxpr = jax.make_jaxpr(mv)(p).jaxpr
    assert _count_primitive(jaxpr, "pallas_call") >= 1
    # no elementwise sub at [B, n*m] outside the kernel
    def _outer_subs(jx):
        subs = 0
        for eqn in jx.eqns:
            if eqn.primitive.name == "sub" and \
                    tuple(eqn.outvars[0].aval.shape) == (B, nm):
                subs += 1
            for v in eqn.params.values():
                if isinstance(v, jax.extend.core.ClosedJaxpr) and \
                        eqn.primitive.name != "pallas_call":
                    subs += _outer_subs(v.jaxpr)
        return subs
    assert _outer_subs(jaxpr) == 0
