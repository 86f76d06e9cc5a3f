"""Plain reference of the marginalized graph kernel: paper Eq. 15 in
float64 on the host.

Same semantics as the program's dense oracle (``mgk_direct``): the
product system

    L_x = diag(d_x / v_x) - A_x * E_x,   K = p_x^T L_x^{-1} (d_x * q_x)

with d_i = sum_j A_ij + q_i, v_x the vertex kernel over node pairs and
E_x the edge kernel over edge pairs. It is built here as a sparse matrix
of the nnz(A) * nnz(A') product edges, not as dense Kronecker products,
and solved by conjugate gradients to a relative residual of 1e-13, whose
true residual is checked afterwards. A 96-node pair then takes a tenth
of a second where the dense solve takes many.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse

from plain import PlainGraph

RTOL = 1e-13
MAX_ITER = 10_000


class ReferenceError(RuntimeError):
    """The reference solve did not reach its tolerance."""


def product_system(g1: PlainGraph, g2: PlainGraph, kv, ke):
    """(L_x as CSR, rhs d_x * q_x, p_x) in float64, row-major product
    index i * m + k."""
    f = lambda a: np.asarray(a, np.float64)        # noqa: E731
    n, m = g1.n_nodes, g2.n_nodes
    d1 = f(g1.adjacency).sum(1) + f(g1.stop_prob)
    d2 = f(g2.adjacency).sum(1) + f(g2.stop_prob)
    dx = np.outer(d1, d2).ravel()
    vx = f(kv(f(g1.vertex_labels)[:, None],
              f(g2.vertex_labels)[None, :])).ravel()
    i, j = np.nonzero(g1.adjacency)
    k, l = np.nonzero(g2.adjacency)
    w = np.outer(f(g1.adjacency[i, j]), f(g2.adjacency[k, l])) * \
        f(ke(f(g1.edge_labels[i, j])[:, None],
             f(g2.edge_labels[k, l])[None, :]))
    rows = (i[:, None] * m + k[None, :]).ravel()
    cols = (j[:, None] * m + l[None, :]).ravel()
    A = scipy.sparse.csr_matrix((w.ravel(), (rows, cols)),
                                shape=(n * m, n * m))
    L = scipy.sparse.diags(dx / vx) - A
    rhs = dx * np.outer(f(g1.stop_prob), f(g2.stop_prob)).ravel()
    px = np.outer(f(g1.start_prob), f(g2.start_prob)).ravel()
    return L.tocsr(), rhs, px


def solve(L, b) -> np.ndarray:
    """Jacobi-preconditioned conjugate gradients in float64."""
    minv = 1.0 / L.diagonal()
    x = np.zeros_like(b)
    r = b.copy()
    z = minv * r
    p = z.copy()
    rz = r @ z
    bnorm = np.linalg.norm(b)
    for _ in range(MAX_ITER):
        if np.linalg.norm(r) <= RTOL * bnorm:
            break
        Ap = L @ p
        alpha = rz / (p @ Ap)
        x += alpha * p
        r -= alpha * Ap
        z = minv * r
        rz, rz_old = r @ z, rz
        p = z + (rz / rz_old) * p
    true_res = np.linalg.norm(b - L @ x) / bnorm
    if not true_res <= 100 * RTOL:
        raise ReferenceError(f"reference CG stopped at relative residual"
                             f" {true_res:.3e}")
    return x


def mgk(g1: PlainGraph, g2: PlainGraph, kv, ke) -> float:
    """The kernel value of one pair; ``kv``/``ke`` are numpy callables
    kappa(x, y) of the vertex and the edge labels."""
    L, rhs, px = product_system(g1, g2, kv, ke)
    return float(px @ solve(L, rhs))
