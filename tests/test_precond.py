"""Kronecker-factored preconditioner (core/precond.py, DESIGN.md §9):

* property suite — factors/assembled M^{-1} are SPD, the batched apply
  matches the dense Kronecker-inverse oracle (core/xmv.py), and
  PCG-with-kron converges to the SAME solution as Jacobi on
  hypothesis-generated graph pairs across all four adaptive routes;
* the tolerance-semantics contract — segmented and lockstep solvers
  declare convergence on the identical preconditioned-residual
  criterion under any ``precond=`` (iterate-for-iterate pin with
  ``precond="kron"``, both PCG variants);
* the point of the subsystem — kron reaches tolerance in FEWER
  iterations than Jacobi on a dense bucket (the BENCH_pcg contract in
  miniature).
"""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import jax
import jax.numpy as jnp

from repro.core import (Constant, CompactPolynomial, KroneckerDelta,
                        SquareExponential, batch_from_graphs)
from repro.core.graph import Graph
from repro.core.mgk import (build_product_system, _make_matvec,
                            mgk_adaptive, mgk_pairs, mgk_pairs_sparse,
                            mgk_pairs_sparse_segmented)
from repro.core.pcg import pcg_solve, pcg_solve_segmented
from repro.core.precond import (kron_apply, kron_factors, kron_scalars,
                                take_kron_factors)
from repro.core.xmv import kron_precond_dense
from repro.data import make_drugbank_like_dataset

VK = Constant(1.0)
VKD = KroneckerDelta(0.4, n_labels=8)
EK = SquareExponential(0.8, rank=12)
CP = CompactPolynomial(0.9)


def _random_pair_batch(B, n, seed, p=0.3, q=0.05, pad_to=None):
    """Random dense-ish labeled graph pairs (the §9 target regime:
    small stopping probability, substantial off-diagonal mass)."""
    rng = np.random.default_rng(seed)
    gs = []
    for _ in range(2 * B):
        nn = int(rng.integers(max(4, n - 4), n + 1))
        a = (rng.random((nn, nn)) < p).astype(np.float32)
        a = np.triu(a, 1)
        a = a + a.T
        e = rng.random((nn, nn)).astype(np.float32)
        e = (e + e.T) / 2 * (a != 0)
        v = rng.integers(0, 4, nn).astype(np.float32)
        gs.append(Graph.create(a, e, v, stop_prob=q))
    pad_to = pad_to or (n + (-n) % 8)
    return (batch_from_graphs(gs[:B], pad_to=pad_to),
            batch_from_graphs(gs[B:], pad_to=pad_to))


# -- factor / oracle properties -------------------------------------------


@settings(max_examples=15, deadline=None)
@given(n=st.integers(6, 20), seed=st.integers(0, 1000),
       p=st.floats(0.1, 0.6))
def test_property_preconditioner_spd_and_matches_oracle(n, seed, p):
    """For random graph pairs: the rank-1 factors are positive, the
    assembled dense M^{-1} is symmetric positive definite (the b-clamp
    certificate), and the batched apply equals oracle @ r."""
    g1, g2 = _random_pair_batch(2, n, seed, p=p)
    B = 2
    N, M = g1.adjacency.shape[1], g2.adjacency.shape[1]
    f1, f2 = kron_factors(g1), kron_factors(g2)
    # rank-1 (diagonal) factors strictly positive
    assert np.all(np.asarray(f1.dinv) > 0)
    assert np.all(np.asarray(f2.dinv) > 0)
    # the similarity row-sum bound keeps sigma < 1 for q > 0
    assert np.all(np.asarray(f1.sigma) < 1.0)
    a, b = kron_scalars(f1, f2, VK, EK)
    assert np.all(np.asarray(b) >= 0)
    apply_ = kron_apply(f1, f2, VK, EK, (B, N, M))
    rng = np.random.default_rng(seed + 1)
    r = jnp.asarray(rng.standard_normal((B, N * M)).astype(np.float32))
    z = np.asarray(apply_(r))
    for i in range(B):
        fi = jax.tree.map(lambda x: x[i], f1)
        fj = jax.tree.map(lambda x: x[i], f2)
        Minv = np.asarray(kron_precond_dense(fi, fj, a[i], b[i]))
        np.testing.assert_allclose(Minv, Minv.T, atol=1e-6)
        ev = np.linalg.eigvalsh(Minv)
        assert ev.min() > 0, f"M^-1 not PD: min eig {ev.min()}"
        np.testing.assert_allclose(z[i], Minv @ np.asarray(r[i]),
                                   rtol=2e-4, atol=1e-6)


def test_rank1_is_diagonal_mean_field():
    """kron_rank=1 keeps only the diagonal Kronecker term — the apply
    must be elementwise (a * dinv ⊗ dinv')."""
    g1, g2 = _random_pair_batch(2, 10, 3)
    B, N, M = 2, g1.adjacency.shape[1], g2.adjacency.shape[1]
    f1, f2 = kron_factors(g1), kron_factors(g2)
    a, _ = kron_scalars(f1, f2, VK, EK)
    apply1 = kron_apply(f1, f2, VK, EK, (B, N, M), rank=1)
    r = jnp.asarray(np.random.default_rng(0).random((B, N * M),)
                    .astype(np.float32))
    dd = (np.asarray(f1.dinv)[:, :, None]
          * np.asarray(f2.dinv)[:, None, :]).reshape(B, -1)
    np.testing.assert_allclose(np.asarray(apply1(r)),
                               np.asarray(a)[:, None] * dd
                               * np.asarray(r), rtol=1e-6)
    with pytest.raises(ValueError):
        kron_apply(f1, f2, VK, EK, (B, N, M), rank=3)


# -- same solution as Jacobi on every adaptive route ----------------------


def _sparse_batches(seed=4):
    gs = [g for g in make_drugbank_like_dataset(16, seed=seed)
          if 8 <= g.n_nodes <= 30][:4]
    return (batch_from_graphs(gs[:2], pad_to=32),
            batch_from_graphs(gs[2:4], pad_to=32))


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 100))
def test_property_kron_matches_jacobi_solution_dense_routes(seed):
    """The preconditioner changes the trajectory, never the solution:
    dense routes (lowrank / pallas) at tight tolerance."""
    g1, g2 = _random_pair_batch(2, 12, seed)
    for method, ek in (("lowrank", EK), ("pallas", CP)):
        rj = mgk_pairs(g1, g2, VKD, ek, method=method, tol=1e-10)
        rk = mgk_pairs(g1, g2, VKD, ek, method=method, tol=1e-10,
                       precond="kron")
        assert bool(np.asarray(rk.converged).all())
        np.testing.assert_allclose(np.asarray(rj.values),
                                   np.asarray(rk.values), rtol=1e-5)


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 100))
def test_property_kron_matches_jacobi_solution_sparse_routes(seed):
    """Sparse route (row-panel), drugbank-like pairs."""
    from repro.kernels.ops import row_panel_packs_for_batch
    g1, g2 = _sparse_batches(seed=4 + seed % 3)
    p1 = row_panel_packs_for_batch(g1)
    p2 = row_panel_packs_for_batch(g2)
    rj = mgk_pairs_sparse(g1, g2, p1, p2, VKD, EK, tol=1e-10)
    rk = mgk_pairs_sparse(g1, g2, p1, p2, VKD, EK, tol=1e-10,
                          precond="kron")
    assert bool(np.asarray(rk.converged).all())
    np.testing.assert_allclose(np.asarray(rj.values),
                               np.asarray(rk.values), rtol=1e-5)


def test_adaptive_routes_accept_precond():
    """mgk_adaptive threads precond to whichever backend wins."""
    g1, g2 = _sparse_batches()
    rj = mgk_adaptive(g1, g2, VKD, EK, tol=1e-10)
    rk = mgk_adaptive(g1, g2, VKD, EK, tol=1e-10, precond="kron")
    np.testing.assert_allclose(np.asarray(rj.values),
                               np.asarray(rk.values), rtol=1e-5)
    d1, d2 = _random_pair_batch(2, 12, 0)
    rjd = mgk_adaptive(d1, d2, VKD, EK, tol=1e-10)
    rkd = mgk_adaptive(d1, d2, VKD, EK, tol=1e-10, precond="kron")
    np.testing.assert_allclose(np.asarray(rjd.values),
                               np.asarray(rkd.values), rtol=1e-5)


def test_unknown_precond_raises():
    g1, g2 = _random_pair_batch(1, 8, 0)
    with pytest.raises(ValueError):
        mgk_pairs(g1, g2, VK, EK, method="lowrank", precond="ilu")


# -- the iteration win (the point of the subsystem) -----------------------


def test_kron_cuts_iterations_on_dense_bucket():
    """On the dense small-q regime the rank-2 preconditioner must beat
    Jacobi by a wide margin (BENCH_pcg asserts ≥30% at bench scale)."""
    g1, g2 = _random_pair_batch(4, 20, 7, p=0.35, q=0.05)
    rj = mgk_pairs(g1, g2, VK, EK, method="lowrank", tol=1e-6)
    rk = mgk_pairs(g1, g2, VK, EK, method="lowrank", tol=1e-6,
                   precond="kron")
    ij = int(np.asarray(rj.iterations).sum())
    ik = int(np.asarray(rk.iterations).sum())
    assert bool(np.asarray(rk.converged).all())
    assert ik < ij, (ij, ik)
    assert 1.0 - ik / ij >= 0.25, f"only {1 - ik / ij:.1%} reduction"
    # rank-1 (mean-field Jacobi) must not beat rank-2
    r1 = mgk_pairs(g1, g2, VK, EK, method="lowrank", tol=1e-6,
                   precond="kron", kron_rank=1)
    assert int(np.asarray(r1.iterations).sum()) >= ik


# -- tolerance semantics: one criterion, every variant, every solver ------


@pytest.mark.parametrize("variant", ["classic", "pipelined"])
def test_segmented_matches_lockstep_with_kron(variant, rng):
    """Iterate-for-iterate pin under precond='kron' (the §9 factor
    remap through the survivor gather), both PCG variants."""
    from repro.kernels.ops import row_panel_packs_for_batch, \
        take_row_panel_pack
    g1, g2 = _sparse_batches()
    p1 = row_panel_packs_for_batch(g1)
    p2 = row_panel_packs_for_batch(g2)
    lock = mgk_pairs_sparse(g1, g2, p1, p2, VKD, EK, tol=1e-10,
                            precond="kron", pcg_variant=variant)
    seg = mgk_pairs_sparse_segmented(g1, g2, p1, p2, VKD, EK, tol=1e-10,
                                     segment_size=4, precond="kron",
                                     pcg_variant=variant)
    assert np.array_equal(np.asarray(lock.iterations),
                          np.asarray(seg.iterations))
    np.testing.assert_allclose(np.asarray(seg.values),
                               np.asarray(lock.values), rtol=1e-6)
    assert int(seg.matvec_pairs) <= int(lock.matvec_pairs)


def test_segmented_gram_tile_with_kron():
    """Gram-tile lockstep vs segmented retirement under kron: the
    per-axis factors expand to per-pair factors alongside the packs."""
    from repro.kernels.ops import row_panel_packs_for_batch
    g1, g2 = _sparse_batches()
    Bi = Bj = 2
    g1u = jax.tree.map(lambda x: x[:Bi], g1)
    g2u = jax.tree.map(lambda x: x[:Bj], g2)
    g1f = jax.tree.map(lambda x: jnp.repeat(x, Bj, axis=0), g1u)
    g2f = jax.tree.map(
        lambda x: jnp.tile(x, (Bi,) + (1,) * (x.ndim - 1)), g2u)
    a1 = row_panel_packs_for_batch(g1u)
    a2 = row_panel_packs_for_batch(g2u)
    lock = mgk_pairs_sparse(g1f, g2f, a1, a2, VKD, EK, tol=1e-10,
                            gram_tile=(Bi, Bj), precond="kron")
    seg = mgk_pairs_sparse_segmented(
        g1f, g2f, a1, a2, VKD, EK, tol=1e-10, segment_size=3,
        gram_tile=(Bi, Bj), precond="kron")
    assert np.array_equal(np.asarray(lock.iterations),
                          np.asarray(seg.iterations))
    assert int(seg.matvec_pairs) <= int(lock.matvec_pairs)
    np.testing.assert_allclose(np.asarray(seg.values),
                               np.asarray(lock.values), rtol=1e-6)


def test_classic_and_pipelined_agree_under_kron():
    """The preconditioned-residual criterion is the IDENTICAL quantity
    in both recurrences (classic rho == pipelined gamma), so iteration
    counts agree within the s-recurrence drift (±1) under kron exactly
    as they do under Jacobi."""
    g1, g2 = _random_pair_batch(3, 14, 11)
    sys_ = build_product_system(g1, g2, VK)
    mv = _make_matvec(g1, g2, sys_, EK, "full", 8)
    f1, f2 = kron_factors(g1), kron_factors(g2)
    B, n = g1.adjacency.shape[0], g1.adjacency.shape[1]
    m = g2.adjacency.shape[1]
    papply = kron_apply(f1, f2, VK, EK, (B, n, m))
    rhs = sys_.dx * sys_.qx
    diag = sys_.dx / sys_.vx
    rc = pcg_solve(mv, rhs, diag, tol=1e-8, precond_apply=papply)
    rp = pcg_solve(mv, rhs, diag, tol=1e-8, precond_apply=papply,
                   variant="pipelined")
    gap = np.abs(np.asarray(rc.iterations)
                 - np.asarray(rp.iterations)).max()
    assert int(gap) <= 1
    np.testing.assert_allclose(np.asarray(rc.x), np.asarray(rp.x),
                               rtol=2e-3, atol=1e-6)


def test_segmented_generic_solver_precond_apply(rng):
    """pcg_solve_segmented with a generic SPD precond_apply and a
    select that rebuilds it: identical iterates to lockstep (the
    solver-level half of the tolerance-semantics contract)."""
    B, N = 6, 16
    a = rng.random((B, N, N)).astype(np.float32)
    spd = np.einsum("bij,bkj->bik", a, a) \
        + N * np.eye(N, dtype=np.float32)[None]
    # spread convergence speeds so retirement actually happens
    spd *= (1.0 + 4.0 * np.arange(B)[:, None, None] / B)
    b = rng.random((B, N)).astype(np.float32)
    diag = jnp.asarray(np.einsum("bii->bi", spd))
    # a simple SPD non-diagonal preconditioner: tridiagonal-ish damp
    m_inv = np.linalg.inv(spd * np.eye(N)[None]
                          + 0.1 * spd * (np.abs(
                              np.arange(N)[:, None]
                              - np.arange(N)[None, :]) == 1))
    m_inv = 0.5 * (m_inv + np.swapaxes(m_inv, 1, 2))
    mv = lambda p: jnp.einsum("bij,bj->bi", spd, p)      # noqa: E731
    ap = lambda r: jnp.einsum("bij,bj->bi", m_inv, r)    # noqa: E731

    def select(lanes):
        idx = np.asarray(lanes)
        sub = spd[idx]
        sub_m = m_inv[idx]
        return (lambda p: jnp.einsum("bij,bj->bi", sub, p),
                lambda r: jnp.einsum("bij,bj->bi", sub_m, r))

    lock = pcg_solve(mv, jnp.asarray(b), diag, tol=1e-9,
                     precond_apply=ap)
    seg = pcg_solve_segmented(mv, jnp.asarray(b), diag, tol=1e-9,
                              segment_size=3, select=select,
                              precond_apply=ap)
    assert np.array_equal(np.asarray(lock.iterations),
                          np.asarray(seg.iterations))
    np.testing.assert_allclose(np.asarray(seg.x), np.asarray(lock.x),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(seg.residual),
                               np.asarray(lock.residual),
                               rtol=1e-5, atol=1e-30)
