"""Gradcheck: the adjoint-PCG custom VJP vs central finite differences,
for EVERY dispatch path of the mgk_adaptive table (DESIGN.md §3.4/§7) —
dense tiling&blocking (pallas), dense low-rank MXU, sparse row-panel
per pair and as a Gram tile — plus the jnp reference backends, over
vertex-kernel params, edge-kernel params, and the stopping probability
``q``. Also pins the cost contract: the gradient jaxpr contains exactly
TWO PCG solves (forward + adjoint)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
import jax.tree_util as jtu

from repro.core import (CompactPolynomial, KroneckerDelta,
                        SquareExponential, batch_from_graphs,
                        kernel_theta, mgk_adaptive_value_and_grad,
                        mgk_value_fn)
from repro.core.mgk import adaptive_route
from repro.data import make_drugbank_like_dataset, make_synthetic_dataset
from repro.kernels.ops import row_panel_packs_for_batch

VK = KroneckerDelta(0.4, n_labels=8)
SE = SquareExponential(1.2, rank=12)
CP = CompactPolynomial(0.9)

RTOL = 1e-3          # the acceptance bar
ATOL = 2e-5          # f32 central-difference noise floor


def _dense_batches():
    gs = make_synthetic_dataset("nws", n_graphs=4, n_nodes=12, seed=0,
                                stop_prob=0.2)
    return (batch_from_graphs(gs[:2], pad_to=16),
            batch_from_graphs(gs[2:], pad_to=16))


def _sparse_batches():
    gs = [g for g in make_drugbank_like_dataset(14, seed=4)
          if 8 <= g.n_nodes <= 30][:4]
    return (batch_from_graphs(gs[:2], pad_to=32),
            batch_from_graphs(gs[2:], pad_to=32))


def _gram_tile_value_fn(ek, **kw):
    """``mgk_value_fn`` over ``_sparse_batches`` as one 2 x 2 Gram tile:
    per-axis packs at t = 16 and the row-major pair-flattened batches,
    as the Gram driver's gradient step builds them."""
    g1u, g2u = _sparse_batches()
    Bi, Bj = g1u.adjacency.shape[0], g2u.adjacency.shape[0]
    g1f = jax.tree.map(lambda x: jnp.repeat(x, Bj, axis=0), g1u)
    g2f = jax.tree.map(lambda x: jnp.tile(x, (Bi,) + (1,) * (x.ndim - 1)),
                       g2u)
    return mgk_value_fn(g1f, g2f, VK, ek, method="sparse",
                        packs1=row_panel_packs_for_batch(g1u, tile=16),
                        packs2=row_panel_packs_for_batch(g2u, tile=16),
                        gram_tile=(Bi, Bj), **kw)


def gradcheck(fn, theta, h0=3e-3, rtol=RTOL, atol=ATOL):
    """Central finite differences of fn(theta).sum() vs jax.grad through
    the custom VJP, leaf by leaf."""
    f = lambda t: fn(t).sum()                          # noqa: E731
    grads = jax.grad(f)(theta)
    leaves, treedef = jtu.tree_flatten(theta)
    grad_leaves = jtu.tree_flatten(grads)[0]
    assert len(leaves) == len(grad_leaves)
    for i, leaf in enumerate(leaves):
        h = h0 * max(1.0, abs(float(leaf)))
        plus, minus = list(leaves), list(leaves)
        plus[i] = leaf + h
        minus[i] = leaf - h
        fd = (float(f(jtu.tree_unflatten(treedef, plus)))
              - float(f(jtu.tree_unflatten(treedef, minus)))) / (2 * h)
        an = float(grad_leaves[i])
        assert an == pytest.approx(fd, rel=rtol, abs=atol), \
            f"leaf {i}: FD {fd} vs adjoint {an}"


# -- dense dispatch paths --------------------------------------------------

@pytest.mark.parametrize("method,ek", [
    ("full", SE),
    ("elementwise", SE),
    ("lowrank", SE),          # adaptive: dense + expansion
    ("pallas", CP),           # adaptive: dense, no expansion
    ("pallas", SE),           # theta threading through the dense kernel
], ids=["full-se", "elementwise-se", "lowrank-se", "pallas-cp",
        "pallas-se"])
def test_dense_paths_match_fd(method, ek):
    g1, g2 = _dense_batches()
    fn = mgk_value_fn(g1, g2, VK, ek, method=method, tol=1e-12)
    gradcheck(fn, kernel_theta(VK, ek, q=0.2))


# -- sparse dispatch paths -------------------------------------------------

@pytest.mark.parametrize("execution,ek", [
    ("per-pair", CP),         # adaptive: sparse, no expansion
    ("per-pair", SE),
    ("gram-tile", SE),        # the Gram driver's step at t = 16
], ids=["vpu-cp", "vpu-se", "gram-tile-se"])
def test_sparse_paths_match_fd(execution, ek):
    if execution == "gram-tile":
        fn = _gram_tile_value_fn(ek, tol=1e-12)
    else:
        g1, g2 = _sparse_batches()
        fn = mgk_value_fn(g1, g2, VK, ek, method="sparse",
                          packs1=row_panel_packs_for_batch(g1),
                          packs2=row_panel_packs_for_batch(g2), tol=1e-12)
    gradcheck(fn, kernel_theta(VK, ek, q=0.05))


def test_adaptive_entry_covers_all_routes():
    """mgk_adaptive_value_and_grad routes through the real dispatch
    table; both a dense and a sparse batch must produce per-pair grads
    for every theta group."""
    gs = [g for g in make_drugbank_like_dataset(14, seed=4)
          if 8 <= g.n_nodes <= 30][:4]
    sparse_wide = (batch_from_graphs(gs[:2], pad_to=64),
                   batch_from_graphs(gs[2:], pad_to=64))
    dense = (_dense_batches(), SE, "lowrank")
    sparse = (sparse_wide, CP, "sparse_vpu")
    for (g1, g2), ek, expected_route in (dense, sparse):
        route, _ = adaptive_route(g1, g2, ek)
        assert route == expected_route
        vals, grads = mgk_adaptive_value_and_grad(g1, g2, VK, ek, q=0.1)
        B = g1.adjacency.shape[0]
        assert vals.shape == (B,)
        assert set(grads) == {"vertex", "edge", "q"}
        for leaf in jtu.tree_leaves(grads):
            assert leaf.shape == (B,)
            assert np.all(np.isfinite(np.asarray(leaf)))


def test_per_pair_grads_sum_to_vjp():
    """The batch VJP (jax.grad of the sum) must equal the sum of the
    per-pair gradients — same adjoint solve, two reductions."""
    g1, g2 = _dense_batches()
    fn = mgk_value_fn(g1, g2, VK, SE, method="lowrank", tol=1e-12)
    theta = kernel_theta(VK, SE, q=0.2)
    total = jax.grad(lambda t: fn(t).sum())(theta)
    _, per_pair = fn.value_and_pair_grads(theta)
    summed = jax.tree.map(lambda a: jnp.sum(a, axis=0), per_pair)
    for a, b in zip(jtu.tree_leaves(total), jtu.tree_leaves(summed)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


# -- Kronecker-preconditioned solves (DESIGN.md §9) ------------------------
#
# precond="kron" changes the PCG trajectory, never the solution, so FD
# parity must hold unchanged on every dispatch route — forward AND
# adjoint solve share the identical SPD M^{-1} closure.

@pytest.mark.parametrize("route", ["lowrank", "pallas", "sparse-vpu",
                                   "sparse-gram-tile"])
def test_kron_precond_paths_match_fd(route):
    if route in ("lowrank", "pallas"):
        g1, g2 = _dense_batches()
        ek = SE if route == "lowrank" else CP
        fn = mgk_value_fn(g1, g2, VK, ek, method=route, tol=1e-12,
                          precond="kron")
        gradcheck(fn, kernel_theta(VK, ek, q=0.2))
        return
    if route == "sparse-gram-tile":
        fn = _gram_tile_value_fn(SE, tol=1e-12, precond="kron")
        gradcheck(fn, kernel_theta(VK, SE, q=0.05))
        return
    g1, g2 = _sparse_batches()
    fn = mgk_value_fn(g1, g2, VK, CP, method="sparse",
                      packs1=row_panel_packs_for_batch(g1),
                      packs2=row_panel_packs_for_batch(g2), tol=1e-12,
                      precond="kron")
    gradcheck(fn, kernel_theta(VK, CP, q=0.05))


def test_kron_adaptive_entry_matches_jacobi_grads():
    """mgk_adaptive_value_and_grad with precond='kron' must produce the
    same per-pair gradients as Jacobi (identical solutions at tight
    tolerance) on both a dense- and a sparse-routed batch."""
    for batches in (_dense_batches(), _sparse_batches()):
        g1, g2 = batches
        vj, gj = mgk_adaptive_value_and_grad(g1, g2, VK, SE, q=0.1,
                                             tol=1e-12)
        vk, gk = mgk_adaptive_value_and_grad(g1, g2, VK, SE, q=0.1,
                                             tol=1e-12, precond="kron")
        np.testing.assert_allclose(np.asarray(vj), np.asarray(vk),
                                   rtol=1e-6)
        for a, b in zip(jtu.tree_leaves(gj), jtu.tree_leaves(gk)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-7)


# -- the cost contract: exactly two PCG solves -----------------------------

def _count_pcg_solves(jaxpr, acc=0):
    """while-loop primitives OUTSIDE pallas kernels == PCG solves (the
    in-kernel fori_loops of the row-panel kernel live inside the
    pallas_call param and are skipped)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            acc += 1
        if "pallas" in eqn.primitive.name:
            continue
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):
                acc = _count_pcg_solves(v.jaxpr, acc)
            elif hasattr(v, "eqns"):
                acc = _count_pcg_solves(v, acc)
    return acc


@pytest.mark.parametrize("make", [
    lambda: (mgk_value_fn(*_dense_batches(), VK, SE, method="lowrank"),
             kernel_theta(VK, SE, q=0.2)),
    lambda: (mgk_value_fn(*_dense_batches(), VK, CP, method="pallas"),
             kernel_theta(VK, CP, q=0.2)),
    lambda: (_gram_tile_value_fn(SE), kernel_theta(VK, SE, q=0.05)),
], ids=["lowrank", "pallas", "sparse-gram-tile"])
def test_exactly_two_pcg_solves_in_grad_jaxpr(make):
    fn, theta = make()
    jaxpr = jax.make_jaxpr(jax.grad(lambda t: fn(t).sum()))(theta)
    assert _count_pcg_solves(jaxpr.jaxpr) == 2


def test_exactly_two_pcg_solves_with_kron_precond():
    """The §9 preconditioner must not add solves: the gradient jaxpr
    still contains exactly two while-loop PCG solves (the M^{-1}
    applications live INSIDE the loop bodies)."""
    g1, g2 = _sparse_batches()
    p1 = row_panel_packs_for_batch(g1)
    p2 = row_panel_packs_for_batch(g2)
    for spec in (dict(method="lowrank"),
                 dict(method="sparse", packs1=p1, packs2=p2)):
        gd, gs = _dense_batches() if spec["method"] == "lowrank" \
            else (g1, g2)
        fn = mgk_value_fn(gd, gs, VK, SE, precond="kron", **spec)
        theta = kernel_theta(VK, SE, q=0.1)
        jaxpr = jax.make_jaxpr(jax.grad(lambda t: fn(t).sum()))(theta)
        assert _count_pcg_solves(jaxpr.jaxpr) == 2


def test_value_matches_nondifferentiable_path():
    """The custom-VJP forward must be bit-compatible (to solver
    tolerance) with the plain mgk_pairs value."""
    from repro.core import mgk_pairs
    g1, g2 = _dense_batches()
    fn = mgk_value_fn(g1, g2, VK, SE, method="lowrank", tol=1e-12)
    vals = fn(kernel_theta(VK, SE))
    ref = mgk_pairs(g1, g2, VK, SE, method="lowrank", tol=1e-12).values
    np.testing.assert_allclose(np.asarray(vals), np.asarray(ref),
                               rtol=1e-6)
