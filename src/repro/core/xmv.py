"""On-the-fly Kronecker product matrix-vector multiplication (XMV).

This module holds the pure-JAX (jnp) implementations of the paper's
Algorithm 2 — the hotspot of the CG solve:

    y[ii'] = sum_{jj'}  A[i,j] * A'[i',j'] * kappa_e(E[i,j], E'[i',j'])
                        * p[jj']

Variants:

* :func:`xmv_full`        — materializes the [n,n,m,m] product; exact oracle
                            for small graphs (the "naive" baseline column of
                            paper Table I, used for validation + benchmarks).
* :func:`xmv_elementwise` — streams over j-chunks, never materializing more
                            than O(n m^2 c) — the jnp analogue of the
                            paper-faithful on-the-fly primitive. The Pallas
                            production kernel (kernels/xmv_dense.py) is the
                            TPU version of this.
* :func:`xmv_lowrank`     — beyond-paper MXU path: with a symmetric feature
                            expansion kappa(x,y) = sum_r phi_r(x) phi_r(y),
                            XMV becomes  y = sum_r (A .* phi_r(E)) P
                            (A' .* phi_r(E'))^T — pure matmuls.

All functions take and return the product-space vector reshaped as a
[n, m] matrix P (row j indexes graph-1 nodes, column j' graph-2 nodes) and
are batched with vmap at the call site.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .base_kernels import BaseKernel

# f32 contractions at full precision: the TPU's default runs f32 matmuls
# as single bf16 passes, ~1e-3 relative error in every matvec
_HIGHEST = jax.lax.Precision.HIGHEST

__all__ = ["xmv_full", "xmv_gram_full", "xmv_elementwise", "xmv_lowrank",
           "weighted_operands", "weighted_operand_grads",
           "kron_precond_dense"]


def _kappa(edge_kernel: BaseKernel, x, y, theta):
    """kappa via ``apply`` when a theta override rides along (traced
    hyperparameters, DESIGN.md §7), else the plain static-param call."""
    if theta is None:
        return edge_kernel(x, y)
    return edge_kernel.apply(x, y, theta)


def xmv_full(A, E, Ap, Ep, P, edge_kernel: BaseKernel, theta=None):
    """Exact XMV via full product materialization. O(n^2 m^2) memory."""
    # K[i, j, ip, jp] = kappa(E[i, j], Ep[ip, jp])
    K = _kappa(edge_kernel, E[:, :, None, None], Ep[None, None, :, :],
               theta)
    W = A[:, :, None, None] * Ap[None, None, :, :] * K
    return jnp.einsum("ijkl,jl->ik", W, P, precision=_HIGHEST)


def xmv_gram_full(A1, E1, A2, E2, P, edge_kernel: BaseKernel, theta=None):
    """Cross-pair oracle for Gram-tile execution: every (i, j) pair of
    a row axis ``A1/E1`` [Bi, n, n] against a column axis ``A2/E2``
    [Bj, m, m], applied to ``P`` [Bi, Bj, n, m] -> [Bi, Bj, n, m].

    A doubly-vmapped :func:`xmv_full` — O(Bi*Bj*n^2*m^2) memory, the
    validation/bench reference for ``kernels.xmv_gram_tile`` only."""
    one = lambda a, e, ap, ep, p: xmv_full(a, e, ap, ep, p,     # noqa
                                           edge_kernel, theta=theta)
    inner = jax.vmap(one, in_axes=(None, None, 0, 0, 0))    # over Bj
    return jax.vmap(inner, in_axes=(0, 0, None, None, 0))(A1, E1, A2,
                                                          E2, P)


def xmv_elementwise(A, E, Ap, Ep, P, edge_kernel: BaseKernel,
                    chunk: int = 8, theta=None):
    """Paper-faithful streaming XMV: scan over length-``chunk`` column
    blocks of (A, E), regenerating kappa products on the fly. Peak temp
    memory O(chunk * n * m^2) instead of O(n^2 m^2).

    ``chunk`` is a memory/throughput knob, not a correctness contract:
    when it does not divide ``n`` it is clamped to the largest divisor of
    ``n`` that fits, so arbitrary bucket sizes work."""
    n, m = A.shape[0], Ap.shape[0]
    if n % chunk:
        chunk = max(c for c in range(1, min(chunk, n) + 1) if n % c == 0)

    def body(carry, j0):
        y = carry
        Aj = jax.lax.dynamic_slice(A, (0, j0), (n, chunk))      # [n, c]
        Ej = jax.lax.dynamic_slice(E, (0, j0), (n, chunk))      # [n, c]
        Pj = jax.lax.dynamic_slice(P, (j0, 0), (chunk, m))      # [c, m]
        # kappa between this chunk's labels and ALL of E': [n, c, m, m]
        K = _kappa(edge_kernel, Ej[:, :, None, None],
                   Ep[None, None, :, :], theta)
        W = Aj[:, :, None, None] * Ap[None, None, :, :] * K
        y = y + jnp.einsum("ickl,cl->ik", W, Pj, precision=_HIGHEST)
        return y, None

    y0 = jnp.zeros((n, m), P.dtype)
    y, _ = jax.lax.scan(body, y0, jnp.arange(0, n, chunk))
    return y


def kron_precond_dense(f1, f2, a, b):
    """Dense oracle for the Kronecker-factored preconditioner
    (DESIGN.md §9): materialize one pair's ``M^{-1}`` as the
    [n*m, n*m] matrix

        M^{-1} = a · diag(dinv ⊗ dinv') + b · (S ⊗ S')

    from single-graph :class:`~repro.core.precond.KronFactors` ``f1``
    (row graph, [n, ...] fields) and ``f2`` (column graph) and the
    pair's scalar coefficients (``precond.kron_scalars``). Row-major
    product flattening (ii' = i·m + i'), matching the solver's
    ``reshape``-based application, so ``oracle @ r`` must equal
    ``kron_apply(r)`` exactly — the validation/bench reference only
    (O(n²m²) memory), never a production path."""
    dd = (f1.dinv[:, None] * f2.dinv[None, :]).reshape(-1)
    return a * jnp.diag(dd) + b * jnp.kron(f1.s, f2.s)


def weighted_operands(A, E, edge_kernel: BaseKernel, theta=None):
    """[R, n, n] stack of (A .* phi_r(E)) for the low-rank path."""
    phi = edge_kernel.features_theta(E, theta) if theta is not None \
        else edge_kernel.features(E)  # [n, n, R]
    if phi is None:
        raise ValueError(
            f"{type(edge_kernel).__name__} has no feature expansion; use the"
            " elementwise path")
    return jnp.einsum("ij,ijr->rij", A, phi)


def weighted_operand_grads(A, E, edge_kernel: BaseKernel,
                           theta=None) -> dict:
    """Per-parameter [R, n, n] stacks of (A .* ∂phi_r(E)/∂θ) — the
    low-rank path's analytic operand derivatives (DESIGN.md §7)."""
    dphi = edge_kernel.dfeatures(E, theta)
    return {name: jnp.einsum("ij,ijr->rij", A, d)
            for name, d in dphi.items()}


def xmv_lowrank(A, E, Ap, Ep, P, edge_kernel: BaseKernel):
    """Beyond-paper MXU 'sandwich' XMV (DESIGN.md §2): two dense matmuls
    per feature rank. FLOPs 2R(n^2 m + n m^2) vs the elementwise path's
    X n^2 m^2 — asymptotically cheaper AND MXU-eligible. Its operations
    carry the named scope ``xmv_lowrank``."""
    with jax.named_scope("xmv_lowrank"):
        WA = weighted_operands(A, E, edge_kernel)     # [R, n, n]
        WAp = weighted_operands(Ap, Ep, edge_kernel)  # [R, m, m]
        return jnp.einsum("rij,jl,rkl->ik", WA, P, WAp,
                          precision=_HIGHEST)


def xmv_lowrank_precomputed(WA, WAp, P):
    """Low-rank XMV with pre-weighted operands (amortized across the CG
    iterations of one solve — the weighting is loop-invariant), in the
    named scope ``xmv_lowrank``."""
    with jax.named_scope("xmv_lowrank"):
        return jnp.einsum("rij,jl,rkl->ik", WA, P, WAp,
                          precision=_HIGHEST)


@partial(jax.jit, static_argnames=("edge_kernel", "method", "chunk"))
def xmv(A, E, Ap, Ep, P, edge_kernel: BaseKernel, method: str = "full",
        chunk: int = 8):
    """Dispatching convenience wrapper (single pair)."""
    if method == "full":
        return xmv_full(A, E, Ap, Ep, P, edge_kernel)
    if method == "elementwise":
        return xmv_elementwise(A, E, Ap, Ep, P, edge_kernel, chunk=chunk)
    if method == "lowrank":
        return xmv_lowrank(A, E, Ap, Ep, P, edge_kernel)
    raise ValueError(f"unknown method {method!r}")
