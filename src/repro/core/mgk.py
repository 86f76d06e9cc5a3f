"""The marginalized graph kernel (paper Eq. 15) — the library's core API.

    K(G, G') = p_x^T (D_x V_x^{-1} - A_x .* E_x)^{-1} D_x q_x

computed with the batched PCG of core/pcg.py and one of the XMV backends:

  method = "full"         exact product materialization (naive baseline)
           "elementwise"  paper-faithful streaming XMV (jnp)
           "lowrank"      beyond-paper MXU sandwich (feature expansion)
           "pallas"       Pallas TPU tiling&blocking kernel
           "pallas_sparse" Pallas block-sparse octile kernel; row-panel
                          packs select the VMEM-staged row-panel kernel
                          whose in-kernel slot reduction runs either
                          elementwise (VPU) or as the MXU low-rank
                          contraction (``sparse_mode``)
           "adaptive"     density-based host dispatch (paper Sec. IV-B)

Batched over pairs: both operands are GraphBatch pytrees of equal batch
size; entry b of the output compares batch1[b] with batch2[b]. The
all-pairs Gram matrix driver lives in distributed/gram.py.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .base_kernels import BaseKernel, Constant, expansion_covers
from .graph import GraphBatch
from .pcg import GuardSpec, MatvecFault, PCGResult, pcg_solve, \
    pcg_solve_segmented
from .xmv import xmv_elementwise, xmv_full, xmv_lowrank_precomputed, \
    weighted_operands

__all__ = ["MGKResult", "mgk_pairs", "mgk_single", "ProductSystem",
           "build_product_system", "mgk_pairs_sparse",
           "mgk_pairs_sparse_segmented", "mgk_adaptive",
           "adaptive_route", "stop_prob_override"]


class ProductSystem(NamedTuple):
    """Diagonal terms of the product-graph linear system, [B, n*m] each."""
    dx: jnp.ndarray      # d (x) d'
    vx: jnp.ndarray      # kappa_v(v_i, v'_i')
    qx: jnp.ndarray      # q (x) q'
    px: jnp.ndarray      # p (x) p'
    mask: jnp.ndarray    # node_mask (x) node_mask'


class MGKResult(NamedTuple):
    values: jnp.ndarray       # [B] kernel values
    iterations: jnp.ndarray   # [B] CG iterations
    converged: jnp.ndarray    # [B]
    nodal: jnp.ndarray | None  # [B, n, m] node-wise similarity (V_x r_inf)
    # scalar: total pair-matvec evaluations of the solve (PCGResult
    # passthrough) — the segmented-vs-lockstep work metric (DESIGN.md §8)
    matvec_pairs: jnp.ndarray | None = None
    # [B] int32 PCG_* status bitmask (PCGResult passthrough, DESIGN.md
    # §10): 0 clean, MAX_ITER slow-but-sane, any cause flag = guard
    # intervened — the Gram driver's degradation-ladder signal
    status: jnp.ndarray | None = None


def _outer_flat(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Batched Kronecker of vectors: [B, n], [B, m] -> [B, n*m]."""
    return (a[:, :, None] * b[:, None, :]).reshape(a.shape[0], -1)


def to_tile_major(v: jnp.ndarray, n: int, m: int, t: int) -> jnp.ndarray:
    """Product vectors ``[..., n*m]`` from node-major (ii' = i*m + i')
    to the tile-major order of the Pallas kernels (``[nt, mt, t, t]``
    flattened, ``kernels.xmv_block_sparse.to_tiles``)."""
    from repro.kernels.xmv_block_sparse import to_tiles
    lead = v.shape[:-1]
    return to_tiles(v.reshape(lead + (n, m)), t).reshape(v.shape)


def from_tile_major(v: jnp.ndarray, n: int, m: int, t: int) -> jnp.ndarray:
    """Inverse of :func:`to_tile_major`."""
    from repro.kernels.xmv_block_sparse import from_tiles
    lead = v.shape[:-1]
    return from_tiles(v.reshape(lead + (n // t, m // t, t, t))
                      ).reshape(v.shape)


def kernel_tile(method: str, packs1=None) -> int | None:
    """Tile edge of the tile-major vector order that a backend's Pallas
    kernel reads and writes, or None for node-major backends. Solves on
    a tiled backend permute the product system ONCE at setup and run
    PCG in that order end to end: PCG is elementwise work plus
    reductions, so it is indifferent to the order (DESIGN.md §2)."""
    if method == "pallas":
        from repro.kernels.xmv_dense import DENSE_TILE
        return DENSE_TILE
    from repro.kernels.xmv_block_sparse import RowPanelPack
    if method == "sparse" and isinstance(packs1, RowPanelPack):
        return packs1.tile
    return None


def tile_major_system(sys_: "ProductSystem", n: int, m: int,
                      t: int | None) -> "ProductSystem":
    """The product system's vectors in the kernels' order (identity for
    ``t=None``)."""
    if t is None:
        return sys_
    return ProductSystem(*(to_tile_major(f, n, m, t) for f in sys_))


def _tile_major_precond(papply, n: int, m: int, t: int | None):
    """Wrap a node-major ``M^{-1}`` apply (the Kronecker factors act on
    ``[n, m]`` matrices) for a tile-major solve."""
    if papply is None or t is None:
        return papply
    return lambda r: to_tile_major(papply(from_tile_major(r, n, m, t)),
                                   n, m, t)


def stop_prob_override(g: GraphBatch, q) -> GraphBatch:
    """Rebuild a batch's stopping probability (and the degrees derived
    from it, paper's d_i = Σ_j A_ij + q_i) from a scalar ``q`` — possibly
    a tracer, the differentiable-hyperparameter path of core/adjoint.py.
    Padding conventions preserved: stop zero-padded, degrees one-padded."""
    stop = q * g.node_mask
    deg = jnp.where(g.node_mask > 0, g.adjacency.sum(-1) + stop,
                    jnp.ones_like(stop))
    return g._replace(stop_prob=stop, degrees=deg)


def build_product_system(g1: GraphBatch, g2: GraphBatch,
                         vertex_kernel: BaseKernel,
                         theta_v=None, q=None) -> ProductSystem:
    """Diagonal terms of the product system. ``theta_v`` overrides the
    vertex kernel's hyperparameters with (possibly traced) values via
    ``BaseKernel.apply``; scalar ``q`` overrides both graphs' stopping
    probability (DESIGN.md §7)."""
    if q is not None:
        g1 = stop_prob_override(g1, q)
        g2 = stop_prob_override(g2, q)
    mask = _outer_flat(g1.node_mask, g2.node_mask)
    x1 = g1.vertex_labels[:, :, None]
    x2 = g2.vertex_labels[:, None, :]
    vx = (vertex_kernel(x1, x2) if theta_v is None
          else vertex_kernel.apply(x1, x2, theta_v)).reshape(mask.shape)
    # padded entries: vx=1, dx=1 keeps the padded diagonal SPD & decoupled
    vx = jnp.where(mask > 0, vx, 1.0)
    dx = _outer_flat(g1.degrees, g2.degrees)
    dx = jnp.where(mask > 0, dx, 1.0)
    qx = _outer_flat(g1.stop_prob, g2.stop_prob) * mask
    px = _outer_flat(g1.start_prob, g2.start_prob) * mask
    return ProductSystem(dx=dx, vx=vx, qx=qx, px=px, mask=mask)


def _make_matvec(g1: GraphBatch, g2: GraphBatch, sys_: ProductSystem,
                 edge_kernel: BaseKernel, method: str, chunk: int,
                 theta_e=None, raw: bool = False):
    """Returns matvec([B, n*m]) applying (D_x V_x^{-1} - A_x .* E_x).

    ``theta_e`` (dict, values possibly traced) overrides the edge
    kernel's hyperparameters on every backend; ``raw=True`` instead
    returns the pure XMV application ``p -> (A_x .* E_x) p`` (no
    diagonal) — the building block of the adjoint parameter contraction
    ``λᵀ (∂A/∂θ) x``, which runs these same backends with kappa replaced
    by ∂kappa/∂θ (core/adjoint.py, DESIGN.md §7).

    ``method="pallas"`` works in the tile-major order of
    :func:`kernel_tile`: ``sys_`` and the vectors are already permuted
    (:func:`tile_major_system`)."""
    B, n = g1.adjacency.shape[0], g1.adjacency.shape[1]
    m = g2.adjacency.shape[1]
    diag = None if raw else sys_.dx / sys_.vx

    if method == "lowrank":
        wo = lambda a, e: weighted_operands(a, e, edge_kernel,   # noqa
                                            theta=theta_e)
        wa = jax.vmap(wo)(g1.adjacency, g1.edge_labels)   # [B, R, n, n]
        wap = jax.vmap(wo)(g2.adjacency, g2.edge_labels)  # [B, R, m, m]

        def matvec(p_vec):
            P = p_vec.reshape(B, n, m)
            y = jax.vmap(xmv_lowrank_precomputed)(wa, wap, P)
            y = y.reshape(B, -1)
            return y if raw else diag * p_vec - y
        return matvec

    if method == "pallas":
        # imported lazily: kernels package depends on core
        from repro.kernels.xmv_block_sparse import xmv_row_panel_batched
        from repro.kernels.xmv_dense import dense_row_panels
        from .base_kernels import pack_theta
        tvec = None if theta_e is None else pack_theta(edge_kernel,
                                                       theta_e)
        t = kernel_tile(method)
        shape = (B, n // t, m // t, t, t)
        # the dense tiling of (A, E) is loop-invariant: built once here
        pk1 = dense_row_panels(g1.adjacency, g1.edge_labels, t)
        pk2 = dense_row_panels(g2.adjacency, g2.edge_labels, t)
        diag_t = None if raw else diag.reshape(shape)

        def matvec(p_vec):
            # fused epilogue: the kernel itself emits diag*p - y, so one
            # launch IS the whole operator application (DESIGN.md §3)
            out = xmv_row_panel_batched(pk1, pk2, p_vec.reshape(shape),
                                        edge_kernel, diag=diag_t,
                                        mode="elementwise", theta=tvec)
            return out.reshape(B, -1)
        return matvec

    if method == "full":
        xmv_one = functools.partial(xmv_full, edge_kernel=edge_kernel,
                                    theta=theta_e)
    elif method == "elementwise":
        xmv_one = functools.partial(xmv_elementwise,
                                    edge_kernel=edge_kernel, chunk=chunk,
                                    theta=theta_e)
    else:
        raise ValueError(f"unknown method {method!r}")

    def matvec(p_vec):
        P = p_vec.reshape(B, n, m)
        y = jax.vmap(lambda a, e, ap, ep, pp: xmv_one(a, e, ap, ep, pp))(
            g1.adjacency, g1.edge_labels, g2.adjacency, g2.edge_labels, P)
        y = y.reshape(B, -1)
        return y if raw else diag * p_vec - y
    return matvec


def _resolve_kron_factors(g1: GraphBatch, g2: GraphBatch,
                          gram_tile: tuple[int, int] | None,
                          factors1=None, factors2=None):
    """Cached-or-derived :class:`~repro.core.precond.KronFactors` for a
    pair batch — the ONE place the gram-tile slicing convention is
    encoded for the preconditioner: under ``gram_tile=(Bi, Bj)`` the
    row-major pair-flattened batches carry the unique row graphs at
    strides of Bj and the unique column graphs as the first Bj entries
    (matching ``distributed.gram._axis_structure``)."""
    from .precond import kron_factors
    if gram_tile is not None:
        Bj = gram_tile[1]
        if factors1 is None:
            factors1 = kron_factors(jax.tree.map(lambda x: x[::Bj], g1))
        if factors2 is None:
            factors2 = kron_factors(jax.tree.map(lambda x: x[:Bj], g2))
        return factors1, factors2
    return (factors1 if factors1 is not None else kron_factors(g1),
            factors2 if factors2 is not None else kron_factors(g2))


def _make_precond_apply(precond: str, g1: GraphBatch, g2: GraphBatch,
                        vertex_kernel: BaseKernel,
                        edge_kernel: BaseKernel,
                        shape: tuple[int, int, int],
                        gram_tile: tuple[int, int] | None = None,
                        factors1=None, factors2=None,
                        kron_rank: int = 2, spd_margin=None):
    """The ``M^{-1}`` application for the PCG solve, shared by every
    entry point and the adjoint path (DESIGN.md §9):

    * ``precond="jacobi"`` -> None (``pcg_solve`` falls back to the
      paper's ``r / diag``);
    * ``precond="kron"`` -> the Kronecker-factored approximate-inverse
      apply of ``core/precond.py``. ``factors1``/``factors2`` are
      optional precomputed :class:`~repro.core.precond.KronFactors`
      (the Gram driver's pack-time cache); without them the factors are
      derived in-trace from the batches — O(B n²), amortized over the
      whole solve. Under ``gram_tile=(Bi, Bj)`` the factors are
      PER-AXIS (row graphs / column graphs), sliced from the row-major
      pair-flattened batches exactly like the per-axis packs.

    ``spd_margin`` (possibly traced) overrides the §9.2 SPD-certificate
    margin; negative values are the certificate-failure injection seam
    (core/precond.py:kron_scalars, DESIGN.md §10).
    """
    if precond == "jacobi":
        return None
    if precond != "kron":
        raise ValueError(f"unknown precond {precond!r}")
    from .precond import kron_apply, kron_apply_gram
    B, n, m = shape
    factors1, factors2 = _resolve_kron_factors(g1, g2, gram_tile,
                                               factors1, factors2)
    if gram_tile is not None:
        Bi, Bj = gram_tile
        return kron_apply_gram(factors1, factors2, vertex_kernel,
                               edge_kernel, (Bi, Bj, n, m),
                               rank=kron_rank, spd_margin=spd_margin)
    return kron_apply(factors1, factors2, vertex_kernel, edge_kernel,
                      (B, n, m), rank=kron_rank, spd_margin=spd_margin)


def _make_sparse_matvec(sys_: ProductSystem, packs1, packs2,
                        edge_kernel: BaseKernel, sparse_mode: str,
                        shape: tuple[int, int, int],
                        theta_e=None, raw: bool = False,
                        gram_tile: tuple[int, int] | None = None):
    """Block-sparse analogue of :func:`_make_matvec` over stacked packs
    (RowPanelPack -> row-panel kernel, TilePack -> legacy batched grid).

    With ``gram_tile=(Bi, Bj)`` the packs are PER-AXIS instead of
    per-pair — ``packs1`` holds the Bi row graphs, ``packs2`` the Bj
    column graphs — and the whole B = Bi*Bj cross-product matvec runs
    as ONE ``xmv_gram_tile`` launch (pair b = bi*Bj + bj, row-major;
    DESIGN.md §8). The [B, n*m] vector contract is unchanged, so the
    PCG solvers and the adjoint path dispatch to it unmodified.

    With ``theta_e``, traced edge hyperparameters reach the kernels two
    ways (DESIGN.md §7): the elementwise mode takes a packed theta
    vector straight into the Pallas kernel; the MXU mode re-derives the
    weighted operands ``values_w`` on device from the pack's structural
    fields (``device_weighted_pack``) — unless the pack already carries
    weights and ``theta_e`` is None, in which case the pack-time host
    precompute is trusted as-is.

    Row-panel packs work in the tile-major order of :func:`kernel_tile`
    (``sys_`` and the vectors already permuted); legacy TilePacks stay
    node-major."""
    from repro.kernels.ops import RowPanelPack, device_weighted_pack, \
        xmv_block_sparse_batched, xmv_gram_tile, xmv_row_panel_batched
    from .base_kernels import pack_theta

    B, n, m = shape
    diag = None if raw else sys_.dx / sys_.vx
    row_panel = isinstance(packs1, RowPanelPack)
    if gram_tile is not None and not row_panel:
        raise ValueError("gram_tile needs RowPanelPack per-axis packs"
                         " (legacy TilePacks have no Gram-tile kernel)")
    tvec = None
    if row_panel:
        have_w = packs1.values_w is not None and \
            packs2.values_w is not None
        # "auto" is the kernels' own rule (_resolve_mode): elementwise
        # (exact, theta via the in-kernel vector), weighted packs or not
        mxu = sparse_mode == "mxu"
        if mxu and (theta_e is not None or not have_w):
            packs1 = device_weighted_pack(packs1, edge_kernel,
                                          theta=theta_e)
            packs2 = device_weighted_pack(packs2, edge_kernel,
                                          theta=theta_e)
        if not mxu and theta_e is not None:
            tvec = pack_theta(edge_kernel, theta_e)
        mode = "mxu" if mxu else "elementwise"

    if not row_panel:
        diag_nm = None if raw else diag.reshape(B, n, m)

        def matvec(p_vec):
            out = xmv_block_sparse_batched(packs1, packs2,
                                           p_vec.reshape(B, n, m),
                                           edge_kernel, diag=diag_nm)
            return out.reshape(B, -1)
        return matvec

    t = packs1.tile
    if gram_tile is not None:
        Bi, Bj = gram_tile
        if Bi * Bj != B:
            raise ValueError(
                f"gram_tile {gram_tile} inconsistent with batch {B}")
        shape, xmv = (Bi, Bj, n // t, m // t, t, t), xmv_gram_tile
    else:
        shape, xmv = (B, n // t, m // t, t, t), xmv_row_panel_batched
    diag_t = None if raw else diag.reshape(shape)

    def matvec(p_vec):
        # with diag: the fused in-kernel epilogue emits diag*p - y (the
        # full operator application); raw mode (diag None) emits +y, the
        # pure XMV the adjoint contraction needs
        out = xmv(packs1, packs2, p_vec.reshape(shape), edge_kernel,
                  diag=diag_t, mode=mode, theta=tvec)
        return out.reshape(B, -1)
    return matvec


@functools.partial(
    jax.jit,
    static_argnames=("vertex_kernel", "edge_kernel", "method", "chunk",
                     "max_iter", "return_nodal", "fixed_iters",
                     "pcg_variant", "precond", "kron_rank", "guard",
                     "fault"))
def mgk_pairs(
    g1: GraphBatch,
    g2: GraphBatch,
    vertex_kernel: BaseKernel = Constant(1.0),
    edge_kernel: BaseKernel = Constant(1.0),
    *,
    method: str = "lowrank",
    chunk: int = 8,
    tol: float = 1e-10,
    max_iter: int = 512,
    return_nodal: bool = False,
    fixed_iters: int | None = None,
    pcg_variant: str = "classic",
    precond: str = "jacobi",
    kron_rank: int = 2,
    guard: GuardSpec | bool | None = True,
    fault: MatvecFault | None = None,
    spd_margin=None,
) -> MGKResult:
    """Marginalized graph kernel between aligned pairs of two batches.

    ``precond``: "jacobi" (paper Alg. 1 line 2) or "kron" — the
    Kronecker-factored approximate inverse of ``core/precond.py``
    (rank ``kron_rank`` ∈ {1, 2}), which cuts PCG iteration counts at
    identical solutions (DESIGN.md §9).

    ``guard``/``fault``/``spd_margin``: PCG numerical guards, the
    matvec fault-injection seam, and the (possibly traced) SPD-margin
    override — see core/pcg.py and DESIGN.md §10. All three reach the
    solve as jit ARGUMENTS (guard/fault static, spd_margin traced), so
    arming them retraces instead of fighting cached traces."""
    B, n = g1.adjacency.shape[0], g1.adjacency.shape[1]
    m = g2.adjacency.shape[1]
    t = kernel_tile(method)
    sys_ = tile_major_system(build_product_system(g1, g2, vertex_kernel),
                             n, m, t)
    matvec = _make_matvec(g1, g2, sys_, edge_kernel, method, chunk)
    rhs = sys_.dx * sys_.qx
    diag = sys_.dx / sys_.vx         # paper Alg. 1 line 2
    papply = _tile_major_precond(
        _make_precond_apply(precond, g1, g2, vertex_kernel, edge_kernel,
                            (B, n, m), kron_rank=kron_rank,
                            spd_margin=spd_margin), n, m, t)
    sol: PCGResult = pcg_solve(matvec, rhs, diag, tol=tol,
                               max_iter=max_iter, fixed_iters=fixed_iters,
                               variant=pcg_variant,
                               precond_apply=papply, guard=guard,
                               fault=fault)
    values = jnp.sum(sys_.px * sol.x, axis=-1)
    nodal = _nodal(sol.x, n, m, t) if return_nodal else None
    return MGKResult(values=values, iterations=sol.iterations,
                     converged=sol.converged, nodal=nodal,
                     matvec_pairs=sol.matvec_pairs, status=sol.status)


def _nodal(x: jnp.ndarray, n: int, m: int, t: int | None) -> jnp.ndarray:
    """The [B, n, m] node-wise similarity from a (possibly tile-major)
    solution vector."""
    if t is not None:
        x = from_tile_major(x, n, m, t)
    return x.reshape(x.shape[0], n, m)


def mgk_single(g1: GraphBatch, g2: GraphBatch, **kw) -> MGKResult:
    """Convenience wrapper for batch size 1."""
    return mgk_pairs(g1, g2, **kw)


def tile_density(batch: GraphBatch, tile: int = 8) -> float:
    """Host-side fraction of non-empty octiles (mean over the batch)."""
    import numpy as np
    from .octile import count_nonempty_tiles
    dens = []
    for b in range(batch.adjacency.shape[0]):
        a = np.asarray(batch.adjacency[b])
        nt = a.shape[0] // tile
        dens.append(count_nonempty_tiles(a, tile) / max(nt * nt, 1))
    return float(np.mean(dens))


def adaptive_route(g1: GraphBatch, g2: GraphBatch,
                   edge_kernel: BaseKernel,
                   density_threshold: float = 0.15,
                   tile: int = 8) -> tuple[str, int]:
    """The adaptive dispatch DECISION (host-side), shared by
    :func:`mgk_adaptive` and the differentiable entry points of
    ``core/adjoint.py`` so both walk the same table:

    =============  ==================  =====================================
    octile dens.   feature expansion   route
    =============  ==================  =====================================
    < threshold    any                 "sparse_vpu"  (row-panel, VPU)
    >= threshold   usable              "lowrank"     (dense MXU sandwich)
    >= threshold   none                "pallas"      (dense tiling kernel)
    =============  ==================  =====================================

    "usable" = ``feature_rank()`` is not None, the rank is small against
    ``density * n``, and the labels stay inside the expansion's accuracy
    domain (the SE Taylor truncation) — otherwise exact elementwise
    paths. Sparse buckets run the row-panel kernels' elementwise body,
    the one "auto" picks (``kernels.xmv_block_sparse._resolve_mode``).
    Returns (route, tile) with ``tile`` shrunk to the largest of
    {tile, 16, 8} dividing the bucket's padded size.
    """
    rank = edge_kernel.feature_rank()
    n, m = g1.adjacency.shape[1], g2.adjacency.shape[1]
    while tile > 8 and (n % tile or m % tile):
        tile //= 2
    dens = max(tile_density(g1, tile), tile_density(g2, tile))
    # the SE Taylor expansion is only accurate within its label domain —
    # outside it, fall back to exact elementwise paths
    if not expansion_covers(edge_kernel, g1.edge_labels, g2.edge_labels):
        rank = None
    rank_usable = rank is not None and rank <= max(16, dens * n)
    if dens < density_threshold:
        return "sparse_vpu", tile
    return ("lowrank" if rank_usable else "pallas"), tile


def mgk_adaptive(g1: GraphBatch, g2: GraphBatch,
                 vertex_kernel: BaseKernel = Constant(1.0),
                 edge_kernel: BaseKernel = Constant(1.0),
                 *, density_threshold: float = 0.15,
                 tile: int = 8,
                 tol: float = 1e-10, max_iter: int = 512,
                 fixed_iters: int | None = None,
                 pcg_variant: str = "classic",
                 precond: str = "jacobi",
                 kron_rank: int = 2,
                 guard: GuardSpec | bool | None = True,
                 fault: MatvecFault | None = None,
                 spd_margin=None) -> MGKResult:
    """The paper's adaptive primitive switch (Sec. IV-B), lifted to the
    bucket level: pick the XMV backend per pair-batch from the octile
    density statistic AND the edge kernel's feature expansion — the
    :func:`adaptive_route` table (DESIGN.md §3.4). ``precond`` rides
    along to whichever backend wins the dispatch."""
    route, tile = adaptive_route(g1, g2, edge_kernel,
                                 density_threshold=density_threshold,
                                 tile=tile)
    kw = dict(tol=tol, max_iter=max_iter, fixed_iters=fixed_iters,
              pcg_variant=pcg_variant, precond=precond,
              kron_rank=kron_rank, guard=guard, fault=fault,
              spd_margin=spd_margin)
    if route.startswith("sparse"):
        from repro.kernels.ops import row_panel_packs_for_batch
        return mgk_pairs_sparse(
            g1, g2, row_panel_packs_for_batch(g1, tile=tile),
            row_panel_packs_for_batch(g2, tile=tile),
            vertex_kernel, edge_kernel, sparse_mode="elementwise", **kw)
    return mgk_pairs(g1, g2, vertex_kernel, edge_kernel, method=route,
                     **kw)


@functools.partial(
    jax.jit,
    static_argnames=("vertex_kernel", "edge_kernel", "max_iter",
                     "return_nodal", "fixed_iters", "pcg_variant",
                     "sparse_mode", "gram_tile", "precond", "kron_rank",
                     "guard", "fault"))
def mgk_pairs_sparse(
    g1: GraphBatch,
    g2: GraphBatch,
    packs1,                      # stacked RowPanelPack or legacy TilePack
    packs2,
    vertex_kernel: BaseKernel = Constant(1.0),
    edge_kernel: BaseKernel = Constant(1.0),
    *,
    sparse_mode: str = "auto",
    tol: float = 1e-10,
    max_iter: int = 512,
    return_nodal: bool = False,
    fixed_iters: int | None = None,
    pcg_variant: str = "classic",
    gram_tile: tuple[int, int] | None = None,
    precond: str = "jacobi",
    kron_rank: int = 2,
    factors1=None,               # optional cached KronFactors (per-pair
    factors2=None,               # stacked, or PER-AXIS under gram_tile)
    guard: GuardSpec | bool | None = True,
    fault: MatvecFault | None = None,
    spd_margin=None,
) -> MGKResult:
    """Block-sparse-octile variant of mgk_pairs (paper Sec. IV).

    The packs are host-preprocessed (``row_panel_packs_for_batch`` /
    ``packs_for_batch`` after reordering) — the quadratic CG work then
    touches only non-empty octiles. GraphBatch still supplies the
    diagonal/probability vectors (cheap, O(n+m)).

    Stacked :class:`~repro.kernels.xmv_block_sparse.RowPanelPack` inputs
    run the row-panel kernel (VMEM tile-row reuse, in-kernel slot
    reduction; ``sparse_mode`` picks "elementwise" / "mxu" / "auto");
    stacked legacy TilePacks run the unrolled-grid baseline. Either way
    the whole bucket's matvec is ONE ``pallas_call`` with the diagonal
    epilogue fused in-kernel (DESIGN.md §3); shares mgk_pairs'
    ``fixed_iters``/``pcg_variant`` contract.

    ``gram_tile=(Bi, Bj)`` switches to Gram-tile execution (DESIGN.md
    §8): ``packs1``/``packs2`` are then PER-AXIS row-panel packs (Bi row
    graphs / Bj column graphs) while ``g1``/``g2`` stay the row-major
    pair-flattened batches of all B = Bi*Bj cross pairs — each matvec is
    one ``xmv_gram_tile`` launch reusing every row graph's panels across
    its Bj partners.

    ``precond="kron"`` solves with the Kronecker-factored approximate
    inverse (core/precond.py, DESIGN.md §9); ``factors1``/``factors2``
    optionally supply pack-time cached factors (per-axis under
    ``gram_tile``, mirroring the per-axis packs)."""
    B, n = g1.adjacency.shape[0], g1.adjacency.shape[1]
    m = g2.adjacency.shape[1]
    t = kernel_tile("sparse", packs1)
    sys_ = tile_major_system(build_product_system(g1, g2, vertex_kernel),
                             n, m, t)
    diag = sys_.dx / sys_.vx
    matvec = _make_sparse_matvec(sys_, packs1, packs2, edge_kernel,
                                 sparse_mode, (B, n, m),
                                 gram_tile=gram_tile)
    papply = _tile_major_precond(
        _make_precond_apply(precond, g1, g2, vertex_kernel, edge_kernel,
                            (B, n, m), gram_tile=gram_tile,
                            factors1=factors1, factors2=factors2,
                            kron_rank=kron_rank, spd_margin=spd_margin),
        n, m, t)

    rhs = sys_.dx * sys_.qx
    sol = pcg_solve(matvec, rhs, diag, tol=tol, max_iter=max_iter,
                    fixed_iters=fixed_iters, variant=pcg_variant,
                    precond_apply=papply, guard=guard, fault=fault)
    values = jnp.sum(sys_.px * sol.x, axis=-1)
    nodal = _nodal(sol.x, n, m, t) if return_nodal else None
    return MGKResult(values=values, iterations=sol.iterations,
                     converged=sol.converged, nodal=nodal,
                     matvec_pairs=sol.matvec_pairs, status=sol.status)


def mgk_pairs_sparse_segmented(
    g1: GraphBatch,
    g2: GraphBatch,
    packs1,                      # stacked (or per-axis) RowPanelPack
    packs2,
    vertex_kernel: BaseKernel = Constant(1.0),
    edge_kernel: BaseKernel = Constant(1.0),
    *,
    sparse_mode: str = "auto",
    tol: float = 1e-10,
    max_iter: int = 512,
    segment_size: int = 32,
    pad_multiple: int = 1,
    pcg_variant: str = "classic",
    gram_tile: tuple[int, int] | None = None,
    return_nodal: bool = False,
    precond: str = "jacobi",
    kron_rank: int = 2,
    factors1=None,
    factors2=None,
    guard: GuardSpec | bool | None = True,
    fault: MatvecFault | None = None,
    spd_margin=None,
) -> MGKResult:
    """:func:`mgk_pairs_sparse` solved with convergence-segmented PCG
    (``core/pcg.py:pcg_solve_segmented``, DESIGN.md §8): the solve runs
    in ``segment_size``-iteration scans and, between segments, pairs
    that converged RETIRE — the matvec batch is compacted by a
    gather/scatter remap of the packs and diagonal terms, so retired
    pairs stop paying matvecs instead of riding along masked.

    Host-driven (each segment is one compiled scan; this entry point
    itself is NOT jittable). With ``gram_tile=(Bi, Bj)`` the FULL
    rectangle runs the single-launch Gram-tile kernel; once pairs
    retire, the surviving (irregular) live set re-gathers per-pair packs
    from the per-axis packs and continues on the per-pair row-panel
    kernel — the usual tail is a handful of slow pairs, exactly where
    per-pair granularity is the right shape. Iterates agree with masked
    lockstep pair-for-pair; ``matvec_pairs`` is strictly smaller
    whenever any pair converges a segment early.

    ``precond="kron"``: the Kronecker preconditioner factors remap
    through the survivor gather/scatter like the packs do (per-axis
    factors expand to per-pair factors alongside the pack expansion),
    preserving the iterate-for-iterate lockstep contract under any
    ``precond=`` (DESIGN.md §9)."""
    from repro.kernels.ops import take_row_panel_pack

    B, n = g1.adjacency.shape[0], g1.adjacency.shape[1]
    m = g2.adjacency.shape[1]
    t = kernel_tile("sparse", packs1)
    sys_ = tile_major_system(build_product_system(g1, g2, vertex_kernel),
                             n, m, t)
    diag = sys_.dx / sys_.vx
    matvec = _make_sparse_matvec(sys_, packs1, packs2, edge_kernel,
                                 sparse_mode, (B, n, m),
                                 gram_tile=gram_tile)
    kron = precond == "kron"
    if kron:
        # materialized HERE (not just inside the apply closure) because
        # select() re-gathers them for every compacted survivor batch
        factors1, factors2 = _resolve_kron_factors(g1, g2, gram_tile,
                                                   factors1, factors2)
    papply = _tile_major_precond(
        _make_precond_apply(precond, g1, g2, vertex_kernel, edge_kernel,
                            (B, n, m), gram_tile=gram_tile,
                            factors1=factors1, factors2=factors2,
                            kron_rank=kron_rank, spd_margin=spd_margin),
        n, m, t)

    def select(lanes):
        import numpy as np
        idx = jnp.asarray(np.asarray(lanes))
        sub_sys = ProductSystem(*(jnp.take(f, idx, axis=0)
                                  for f in sys_))
        if gram_tile is not None:
            # expand the per-axis packs to per-pair packs for the
            # irregular survivor set (pair b = bi*Bj + bj, row-major)
            Bi, Bj = gram_tile
            i1, i2 = idx // Bj, idx % Bj
            p1 = take_row_panel_pack(packs1, i1)
            p2 = take_row_panel_pack(packs2, i2)
        else:
            i1 = i2 = idx
            p1 = take_row_panel_pack(packs1, idx)
            p2 = take_row_panel_pack(packs2, idx)
        sub_mv = _make_sparse_matvec(sub_sys, p1, p2, edge_kernel,
                                     sparse_mode, (len(lanes), n, m))
        if not kron:
            return sub_mv
        # the preconditioner factors remap through the survivor gather
        # exactly like the packs (per-axis -> per-pair expansion
        # included); the per-pair scalars are recomputed from the same
        # gathered stats, so the compacted trajectory stays
        # iterate-for-iterate identical to lockstep
        from .precond import kron_apply, take_kron_factors
        sub_apply = kron_apply(take_kron_factors(factors1, i1),
                               take_kron_factors(factors2, i2),
                               vertex_kernel, edge_kernel,
                               (len(lanes), n, m), rank=kron_rank,
                               spd_margin=spd_margin)
        return sub_mv, _tile_major_precond(sub_apply, n, m, t)

    rhs = sys_.dx * sys_.qx
    sol = pcg_solve_segmented(matvec, rhs, diag, tol=tol,
                              max_iter=max_iter,
                              segment_size=segment_size,
                              variant=pcg_variant, select=select,
                              pad_multiple=pad_multiple,
                              precond_apply=papply, guard=guard,
                              fault=fault)
    values = jnp.sum(sys_.px * sol.x, axis=-1)
    nodal = _nodal(sol.x, n, m, t) if return_nodal else None
    return MGKResult(values=values, iterations=sol.iterations,
                     converged=sol.converged, nodal=nodal,
                     matvec_pairs=sol.matvec_pairs, status=sol.status)
