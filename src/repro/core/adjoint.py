"""Differentiable MGK: hyperparameter gradients via an adjoint PCG solve.

The paper's closing claim — kernel-based learning at scale — needs
``∂K/∂θ`` for the vertex/edge base-kernel hyperparameters and the
stopping probability ``q``. Nothing in the solver is natively
reverse-differentiable (``pcg_solve`` is a ``lax.while_loop``; the
Pallas kernels bake parameters in as static arguments), and unrolling
CG for autodiff would store every iterate. This module instead wraps
the solve in a ``jax.custom_vjp`` built on the implicit function
theorem (DESIGN.md §7):

    K = p_xᵀ x,     A(θ) x = b(θ),   A = D_x V_x^{-1} - A_x ∘ E_x

    x̄ = v̄ p_x
    Aᵀ λ = x̄                      -> ONE adjoint PCG solve; A is
                                     symmetric, so the adjoint system
                                     reuses the forward matvec closure
                                     (and Pallas kernels, and packs)
                                     unchanged (pcg.adjoint_solve)
    θ̄  = λᵀ (∂b/∂θ) - λᵀ (∂A/∂θ) x

The parameter contractions never materialize ∂A:

* vertex params and q only touch the DIAGONAL (and b): elementwise
  expressions in λ, x and the analytic ``dtheta()`` hooks of
  core/base_kernels.py.
* edge params enter through the off-diagonal ``A_x ∘ E_x``, whose
  θ-derivative has A's sparsity: ``λᵀ (∂A_x∘E_x) x`` is ONE raw XMV of
  x with kappa replaced by ∂kappa/∂θ (``ParamDerivative``) — the same
  dispatch backend as the forward solve — followed by a dot with λ.

Cost: gradients w.r.t. ALL hyperparameters ≈ one extra PCG solve per
pair (the acceptance contract: exactly two solves in the jaxpr — tested
in tests/test_grad.py) plus one XMV per edge parameter.

Usage note: the factory closes the (concrete) graph batches and packs
over the custom_vjp function, so build the value function OUTSIDE any
jit trace and differentiate with respect to ``theta`` only::

    fn = mgk_value_fn(g1, g2, vk, ek, method="lowrank")
    theta = kernel_theta(vk, ek, q=0.05)
    vals, grads = jax.value_and_grad(lambda t: fn(t).sum())(theta)

Inner computations (PCG, the XMV kernels) stay jitted as always.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from .base_kernels import BaseKernel, Constant, ParamDerivative
from .graph import GraphBatch
from .mgk import _make_matvec, _make_precond_apply, _make_sparse_matvec, \
    _outer_flat, _tile_major_precond, adaptive_route, build_product_system, \
    kernel_tile, stop_prob_override, tile_major_system, to_tile_major
from .pcg import adjoint_solve, pcg_solve
from .xmv import xmv_lowrank_precomputed, weighted_operand_grads, \
    weighted_operands

__all__ = ["kernel_theta", "mgk_value_fn", "mgk_pairs_value_and_grad",
           "mgk_pairs_sparse_value_and_grad",
           "mgk_adaptive_value_and_grad", "flatten_grads"]


def kernel_theta(vertex_kernel: BaseKernel, edge_kernel: BaseKernel,
                 q: float | None = None) -> dict:
    """The canonical hyperparameter pytree the gradient entry points
    differentiate against: ``{"vertex": {...}, "edge": {...}[, "q"]}``
    seeded from the kernels' current (static) values. Drop keys to
    freeze groups; include ``q`` to make the stopping probability a
    learnable global scalar (it overrides both batches' ``stop_prob``
    and the degrees derived from it)."""
    theta = {"vertex": vertex_kernel.theta(), "edge": edge_kernel.theta()}
    if q is not None:
        theta["q"] = jnp.asarray(q, jnp.float32)
    return theta


def flatten_grads(grads: dict) -> dict:
    """``{"vertex": {"h": g}, "edge": {"alpha": g}, "q": g}`` ->
    ``{"vertex.h": g, "edge.alpha": g, "q": g}`` (the storage layout of
    Gram gradient blocks, distributed/gram.py)."""
    flat = {}
    for group, val in grads.items():
        if isinstance(val, dict):
            for name, g in val.items():
                flat[f"{group}.{name}"] = g
        else:
            flat[group] = val
    return flat


def mgk_value_fn(
    g1: GraphBatch,
    g2: GraphBatch,
    vertex_kernel: BaseKernel = Constant(1.0),
    edge_kernel: BaseKernel = Constant(1.0),
    *,
    method: str = "lowrank",
    packs1=None,
    packs2=None,
    chunk: int = 8,
    tol: float = 1e-10,
    max_iter: int = 512,
    fixed_iters: int | None = None,
    pcg_variant: str = "classic",
    gram_tile: tuple[int, int] | None = None,
    precond: str = "jacobi",
    kron_rank: int = 2,
    precond_factors: tuple | None = None,
) -> Callable:
    """Build ``value(theta) -> [B]`` for aligned pair batches, wrapped in
    the adjoint-solve ``jax.custom_vjp``.

    ``method``: any dense backend of :func:`~repro.core.mgk.mgk_pairs`
    ("full" / "elementwise" / "lowrank" / "pallas") or "sparse" with
    stacked row-panel ``packs1``/``packs2`` (as in
    :func:`~repro.core.mgk.mgk_pairs_sparse`; theta's edge values reach
    the kernel as its in-kernel theta vector).
    ``gram_tile=(Bi, Bj)``: the packs are PER-AXIS and both the forward
    and adjoint solves — plus the edge-gradient contraction — run on the
    single-launch Gram-tile kernel (g1/g2 stay the row-major
    pair-flattened batches, as in ``mgk_pairs_sparse``).

    ``precond="kron"``: BOTH the forward and the adjoint solve run with
    the Kronecker-factored approximate inverse (DESIGN.md §9). The
    factors are built ONCE here from the concrete batches (or taken
    from ``precond_factors``, the Gram driver's pack-time cache) and
    the identical SPD ``M^{-1}`` closure serves both solves — the
    preconditioner shapes only the solve trajectory, so gradients and
    the exactly-two-solves jaxpr pin are untouched. The factors use the
    batches' PACK-TIME degrees: a traced ``q`` override still reaches
    the operator and the right-hand side exactly (correctness), it just
    doesn't re-derive the preconditioner statistics (iteration count
    only).

    The returned callable carries ``value_and_pair_grads(theta)``
    returning per-pair gradients (``[B]`` leaves) from the same single
    forward + adjoint solve pair.
    """
    sparse = method in ("sparse", "pallas_sparse")
    if sparse and (packs1 is None or packs2 is None):
        raise ValueError("method='sparse' needs stacked RowPanelPack"
                         " packs1/packs2")
    B, n = g1.adjacency.shape[0], g1.adjacency.shape[1]
    m = g2.adjacency.shape[1]
    # Pallas backends solve (forward AND adjoint) in the kernels'
    # tile-major vector order; every product-space vector built below
    # goes through `tiled` (identity for node-major backends)
    t = kernel_tile("sparse" if sparse else method, packs1)

    def tiled(v):
        return v if t is None else to_tile_major(v, n, m, t)

    pf1, pf2 = precond_factors if precond_factors is not None \
        else (None, None)
    papply = _tile_major_precond(
        _make_precond_apply(precond, g1, g2, vertex_kernel, edge_kernel,
                            (B, n, m), gram_tile=gram_tile, factors1=pf1,
                            factors2=pf2, kron_rank=kron_rank), n, m, t)
    solve_kw = dict(tol=tol, max_iter=max_iter, fixed_iters=fixed_iters,
                    variant=pcg_variant, precond_apply=papply)

    def _parts(theta):
        tv = theta.get("vertex") or None
        te = theta.get("edge") or None
        q = theta.get("q")
        return tv, te, q

    def _build_mv(theta, sys_):
        _, te, _ = _parts(theta)
        if sparse:
            return _make_sparse_matvec(sys_, packs1, packs2, edge_kernel,
                                       (B, n, m), theta_e=te,
                                       gram_tile=gram_tile)
        return _make_matvec(g1, g2, sys_, edge_kernel, method, chunk,
                            theta_e=te)

    def _system(theta):
        tv, _, q = _parts(theta)
        sys_ = tile_major_system(
            build_product_system(g1, g2, vertex_kernel, theta_v=tv, q=q),
            n, m, t)
        return sys_, _build_mv(theta, sys_)

    def _solve(theta):
        sys_, mv = _system(theta)
        rhs = sys_.dx * sys_.qx
        diag = sys_.dx / sys_.vx
        sol = pcg_solve(mv, rhs, diag, **solve_kw)
        return sol, sys_, mv

    # -- the adjoint backward pass --------------------------------------
    def _edge_grads(te, x, names):
        """{name: raw XMV of x with kappa -> ∂kappa/∂θ_name} for ALL
        edge parameters: the sparsity-preserving half of λᵀ (∂A/∂θ) x,
        [B, n*m] per name (x and the result in the solve's vector
        order). Parameter-independent operand derivation (the
        lowrank weighted operands) is hoisted out of the per-name loop —
        it already carries every parameter's slice."""
        if sparse:
            return {name: _make_sparse_matvec(
                None, packs1, packs2, ParamDerivative(edge_kernel, name),
                (B, n, m), theta_e=te, raw=True, gram_tile=gram_tile)(x)
                for name in names}
        if method == "lowrank":
            wo = lambda a, e: weighted_operands(a, e, edge_kernel,  # noqa
                                                theta=te)
            dwo = lambda a, e: weighted_operand_grads(               # noqa
                a, e, edge_kernel, theta=te)
            wa = jax.vmap(wo)(g1.adjacency, g1.edge_labels)
            wap = jax.vmap(wo)(g2.adjacency, g2.edge_labels)
            dwa = jax.vmap(dwo)(g1.adjacency, g1.edge_labels)
            dwap = jax.vmap(dwo)(g2.adjacency, g2.edge_labels)
            x_mat = x.reshape(B, n, m)
            return {name: (
                jax.vmap(xmv_lowrank_precomputed)(dwa[name], wap, x_mat)
                + jax.vmap(xmv_lowrank_precomputed)(wa, dwap[name],
                                                    x_mat)
            ).reshape(B, -1) for name in names}
        return {name: _make_matvec(
            g1, g2, None, ParamDerivative(edge_kernel, name), method,
            chunk, theta_e=te, raw=True)(x) for name in names}

    def _pair_grads(theta, x, ct, sys_, mv):
        """Per-pair hyperparameter gradients, [B] leaves mirroring
        ``theta``; ``ct`` [B] scales the adjoint right-hand side (ones
        for raw per-pair gradients, the upstream cotangent in the VJP).
        ``sys_``/``mv`` are the forward solve's product system and
        matvec closure, reused — not rebuilt — for the adjoint."""
        tv, te, q = _parts(theta)
        diag = sys_.dx / sys_.vx
        lam = adjoint_solve(mv, ct[:, None] * sys_.px, diag,
                            **solve_kw).x
        grads: dict = {}
        if "vertex" in theta:
            x1 = g1.vertex_labels[:, :, None]
            x2 = g2.vertex_labels[:, None, :]
            dv = vertex_kernel.dtheta(x1, x2, tv)
            # ∂A = diag(-dx vx^{-2} ∂vx)  =>  -λᵀ(∂A)x elementwise
            coeff = lam * x * sys_.dx / (sys_.vx * sys_.vx)
            grads["vertex"] = {
                name: jnp.sum(
                    coeff * tiled(dv[name].reshape(B, -1)) * sys_.mask,
                    axis=-1)
                for name in theta["vertex"]}
        if "edge" in theta:
            # ∂A = -(A_x ∘ ∂kappa E_x)  =>  -λᵀ(∂A)x = +λᵀ XMV_∂kappa(x)
            ys = _edge_grads(te, x, tuple(theta["edge"]))
            grads["edge"] = {
                name: jnp.sum(lam * ys[name], axis=-1)
                for name in theta["edge"]}
        if "q" in theta and q is None:
            grads["q"] = None
        elif "q" in theta:
            g1q = stop_prob_override(g1, q)
            g2q = stop_prob_override(g2, q)
            # ∂dx = maskx (m ⊗ d' + d ⊗ m');  qx = q² maskx
            dxq = sys_.mask * tiled(
                _outer_flat(g1.node_mask, g2q.degrees)
                + _outer_flat(g1q.degrees, g2.node_mask))
            drhs = dxq * sys_.qx + sys_.dx * 2.0 * q * sys_.mask
            ddiag = dxq / sys_.vx
            grads["q"] = jnp.sum(lam * (drhs - x * ddiag), axis=-1)
        return grads

    @jax.custom_vjp
    def value(theta):
        sol, sys_, _ = _solve(theta)
        return jnp.sum(sys_.px * sol.x, axis=-1)

    def value_fwd(theta):
        # residuals: theta, the solution, and the product system (plain
        # arrays) — the backward pass rebuilds only the matvec closure
        sol, sys_, _ = _solve(theta)
        return jnp.sum(sys_.px * sol.x, axis=-1), (theta, sol.x, sys_)

    def value_bwd(res, ct):
        theta, x, sys_ = res
        grads = _pair_grads(theta, x, ct, sys_, _build_mv(theta, sys_))
        return (jax.tree.map(lambda a: jnp.sum(a, axis=0), grads),)

    value.defvjp(value_fwd, value_bwd)

    def value_and_pair_grads(theta, with_aux: bool = False):
        """(values [B], per-pair grads) from ONE forward + ONE adjoint
        solve sharing one system/matvec build; ``with_aux`` appends the
        forward :class:`PCGResult` (iteration counts / convergence for
        the Gram driver's block records)."""
        sol, sys_, mv = _solve(theta)
        vals = jnp.sum(sys_.px * sol.x, axis=-1)
        grads = _pair_grads(theta, sol.x, jnp.ones_like(vals), sys_, mv)
        if with_aux:
            return vals, grads, sol
        return vals, grads

    value.value_and_pair_grads = value_and_pair_grads
    return value


def mgk_pairs_value_and_grad(
    g1: GraphBatch, g2: GraphBatch, theta: dict | None = None,
    vertex_kernel: BaseKernel = Constant(1.0),
    edge_kernel: BaseKernel = Constant(1.0), **spec,
) -> tuple[jnp.ndarray, dict]:
    """(values [B], per-pair grads) for the dense backends — the
    ``value_and_grad``-style companion of ``mgk_pairs``. ``theta``
    defaults to :func:`kernel_theta` of the two kernels (no ``q``)."""
    theta = kernel_theta(vertex_kernel, edge_kernel) \
        if theta is None else theta
    fn = mgk_value_fn(g1, g2, vertex_kernel, edge_kernel, **spec)
    return fn.value_and_pair_grads(theta)


def mgk_pairs_sparse_value_and_grad(
    g1: GraphBatch, g2: GraphBatch, packs1, packs2,
    theta: dict | None = None,
    vertex_kernel: BaseKernel = Constant(1.0),
    edge_kernel: BaseKernel = Constant(1.0), **spec,
) -> tuple[jnp.ndarray, dict]:
    """Sparse (row-panel) companion of ``mgk_pairs_sparse``."""
    theta = kernel_theta(vertex_kernel, edge_kernel) \
        if theta is None else theta
    fn = mgk_value_fn(g1, g2, vertex_kernel, edge_kernel,
                      method="sparse", packs1=packs1, packs2=packs2,
                      **spec)
    return fn.value_and_pair_grads(theta)


def mgk_adaptive_value_and_grad(
    g1: GraphBatch, g2: GraphBatch,
    vertex_kernel: BaseKernel = Constant(1.0),
    edge_kernel: BaseKernel = Constant(1.0),
    theta: dict | None = None,
    *,
    q: float | None = None,
    density_threshold: float = 0.15,
    tile: int = 8,
    tol: float = 1e-10,
    max_iter: int = 512,
    fixed_iters: int | None = None,
    pcg_variant: str = "classic",
    precond: str = "jacobi",
    kron_rank: int = 2,
) -> tuple[jnp.ndarray, dict]:
    """Adaptive-dispatch companion of ``mgk_adaptive``: route through
    the :func:`~repro.core.mgk.adaptive_route` table, then compute
    (values, per-pair hyperparameter grads) with the adjoint solve on
    whichever backend the table picked. ``precond`` rides along to the
    winning backend's forward AND adjoint solves."""
    theta = kernel_theta(vertex_kernel, edge_kernel, q=q) \
        if theta is None else theta
    route, tile = adaptive_route(g1, g2, edge_kernel,
                                 density_threshold=density_threshold,
                                 tile=tile)
    kw = dict(tol=tol, max_iter=max_iter, fixed_iters=fixed_iters,
              pcg_variant=pcg_variant, precond=precond,
              kron_rank=kron_rank)
    if route.startswith("sparse"):
        from repro.kernels.ops import row_panel_packs_for_batch
        fn = mgk_value_fn(
            g1, g2, vertex_kernel, edge_kernel, method="sparse",
            packs1=row_panel_packs_for_batch(g1, tile=tile),
            packs2=row_panel_packs_for_batch(g2, tile=tile), **kw)
    else:
        fn = mgk_value_fn(g1, g2, vertex_kernel, edge_kernel,
                          method=route, **kw)
    return fn.value_and_pair_grads(theta)
