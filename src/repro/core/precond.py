"""Kronecker-factored approximate-inverse preconditioning (DESIGN.md §9).

PRs 1-4 drove the cost of one PCG matvec down; what remains is how MANY
matvecs a solve needs, which the paper's plain Jacobi preconditioner
(Algorithm 1 line 2, ``M = diag(A) = D_x V_x^{-1}``) leaves on the
table: it ignores the tensor-product structure of the generalized
Laplacian entirely. This module builds the structured alternative.

Derivation (§9.1). The product system is

    A = D_x V_x^{-1} - A_x ∘ E_x,      D_x = D ⊗ D'

with ``D = diag(d)`` the per-graph degree matrices. Factoring the
diagonal out and expanding the inverse as a Neumann series,

    A^{-1} = (I - V_x D_x^{-1} (A_x ∘ E_x))^{-1} V_x D_x^{-1}
           ≈ V_x D_x^{-1} + V_x D_x^{-1} (A_x ∘ E_x) V_x D_x^{-1} + ...

Under the mean-field closure ``V_x ≈ v̄ I``, ``E_x ≈ κ̄`` (the label
statistics of the pair), the first-order truncation IS a rank-2
Kronecker sum of per-graph factors:

    M^{-1} = a (D^{-1} ⊗ D'^{-1}) + b (S ⊗ S'),
    S = D^{-1} A D^{-1},   a = v̄,   b = v̄² κ̄.

Why it works (§9.1): in the symmetrized space the Jacobi-preconditioned
spectrum is ``1 - μ λᵢ μⱼ`` with ``λᵢ μⱼ`` the eigenvalue products of
the two normalized adjacencies ``Ã = D^{-1/2} A D^{-1/2}`` and
``μ = v̄ κ̄``; the rank-2 preconditioner maps it to
``(1 - μx)(a + bx) ≈ 1 - μ²x²`` — the condition number drops by
``(1 + μρρ')²``, which for the near-critical small-``q`` regime the
paper's datasets live in is the difference between tens and hundreds of
CG iterations.

SPD guarantee. ``S ⊗ S'`` alone is indefinite (adjacency spectra are
two-sided), so ``b`` is clamped with each graph's PACK-TIME spectral
bound ``σ = ρ(Ã) ≤ max_i Σ_j |A_ij| / sqrt(d_i d_j)`` (Gershgorin):

    b ≤ spd_margin · a / (σ σ')   =>   M^{-1} ≻ 0.

Everything per-graph — ``S``, ``1/d``, ``σ``, the label means — is a
pure function of (adjacency, degrees, labels): computed once at pack
time, cached on :class:`~repro.distributed.gram.GraphPackCache`
alongside the octile packs, and stacked per pair batch or PER AXIS for
Gram-tile execution (mirroring ``stacked_axis``). The pair-level
scalars ``a``/``b`` are two kernel evaluations on label means.

Application cost. ``M^{-1} r`` on the reshaped residual is one
elementwise product plus one batched ``[n,n] @ X @ [m,m]`` sandwich —
two small dense matmuls per pair, exactly the MXU-friendly shape this
codebase is built around; no new sparse format, no extra HBM-resident
operator. The preconditioner changes ONLY the solve trajectory, never
the solution, so the adjoint VJP (core/adjoint.py) reuses the identical
SPD ``M^{-1}`` for its backward solve and gradients are untouched.

The dense oracle lives in ``core/xmv.py:kron_precond_dense`` (the
validation reference of tests/test_precond.py).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = ["KronFactors", "kron_factors", "kron_factor_arrays",
           "kron_scalars", "kron_apply", "kron_apply_gram",
           "take_kron_factors", "stack_kron_factors"]

# floor for v̄ (keeps a > 0 for degenerate/padded pairs) and for the
# σσ' denominator of the SPD clamp (zero-edge graphs have σ = 0)
_VBAR_FLOOR = 1e-6
_SIGMA_FLOOR = 1e-6
# default SPD safety margin: b ≤ margin · a / (σ σ')
SPD_MARGIN = 0.95


class KronFactors(NamedTuple):
    """Per-graph Kronecker-preconditioner factors (any leading batch
    axes; the Gram driver caches the per-graph [n, ...] slices and
    stacks them per pair batch or per Gram-tile axis).

    s:     [..., n, n] ``D^{-1} A D^{-1}`` — the rank-2 term's factor.
    dinv:  [..., n]    ``1 / d`` — the rank-1 (diagonal) factor.
    sigma: [...]       Gershgorin bound on ``ρ(D^{-1/2} A D^{-1/2})``,
                       the pack-time ingredient of the SPD clamp.
    emean: [...]       mean edge label over nonzero adjacency entries.
    vmean: [...]       node-mask-weighted mean vertex label.

    The label means feed the pair-time mean-field scalars
    (:func:`kron_scalars`); they are statistics, not operands — the
    preconditioner only shapes the solve trajectory, so a crude closure
    costs iterations, never correctness.
    """
    s: jnp.ndarray
    dinv: jnp.ndarray
    sigma: jnp.ndarray
    emean: jnp.ndarray
    vmean: jnp.ndarray

    @property
    def n(self) -> int:
        return self.s.shape[-1]


def kron_factor_arrays(adjacency, degrees, edge_labels, vertex_labels,
                       node_mask) -> KronFactors:
    """Factors from raw graph arrays (works batched or per-graph, jnp or
    numpy in / jnp out). The ONE implementation shared by the in-trace
    path (:func:`kron_factors` on a GraphBatch) and the Gram driver's
    host-side pack cache."""
    A = jnp.asarray(adjacency)
    d = jnp.asarray(degrees)
    dinv = 1.0 / d
    s = dinv[..., :, None] * A * dinv[..., None, :]
    # ρ(Ã) bound via the SIMILAR matrix D^{-1} A (same spectrum as the
    # symmetrized Ã = D^{-1/2} A D^{-1/2}): ρ ≤ ||D^{-1}|A|||_∞
    # = max_i Σ_j |A_ij| / d_i. With the paper's degrees
    # d_i = Σ_j A_ij + q_i this is 1 - min_i q_i/d_i < 1 — far tighter
    # than Gershgorin on Ã itself, whose √(d_i d_j) cross terms
    # overshoot past 1 on degree-heterogeneous graphs (padded rows:
    # A = 0, d = 1 contribute 0)
    sigma = jnp.max(jnp.sum(jnp.abs(A), axis=-1) * dinv, axis=-1)
    nz = (A != 0).astype(d.dtype)
    cnt = jnp.sum(nz, axis=(-2, -1))
    emean = jnp.sum(jnp.asarray(edge_labels) * nz, axis=(-2, -1)) \
        / jnp.maximum(cnt, 1.0)
    mask = jnp.asarray(node_mask)
    vmean = jnp.sum(jnp.asarray(vertex_labels) * mask, axis=-1) \
        / jnp.maximum(jnp.sum(mask, axis=-1), 1.0)
    return KronFactors(s=s, dinv=dinv, sigma=sigma, emean=emean,
                       vmean=vmean)


def kron_factors(g) -> KronFactors:
    """Factors for every graph of a :class:`GraphBatch` (leading [B]
    axis on each field). Pure jnp — safe inside jit traces, so the
    non-cached entry points (``mgk_pairs``/``mgk_pairs_sparse`` without
    driver factors) build factors on the fly at O(B n²) cost, amortized
    over the whole solve."""
    return kron_factor_arrays(g.adjacency, g.degrees, g.edge_labels,
                              g.vertex_labels, g.node_mask)


def take_kron_factors(f: KronFactors, indices) -> KronFactors:
    """Gather stacked factors along the leading batch axis — the
    segmented-PCG pair-retirement remap and the Gram-tile -> per-pair
    expansion, mirroring ``ops.take_row_panel_pack``."""
    idx = jnp.asarray(indices)
    return KronFactors(*(jnp.take(x, idx, axis=0) for x in f))


def stack_kron_factors(factors: list[KronFactors]) -> KronFactors:
    """Stack per-graph factors to a leading [B] axis (same-bucket
    graphs => same shapes) — the pack-cache stacking hook."""
    return KronFactors(*(jnp.stack([getattr(f, name) for f in factors])
                         for name in KronFactors._fields))


def kron_scalars(f1: KronFactors, f2: KronFactors, vertex_kernel,
                 edge_kernel, spd_margin: float | None = None,
                 outer: bool = False):
    """Pair-level mean-field scalars ``(a, b)`` of the §9 expansion:
    ``a = v̄``, ``b = min(v̄² κ̄, spd_margin · a / (σ σ'))``.

    ``v̄``/``κ̄`` are the base kernels evaluated on the factors' label
    means — two scalar kernel calls per pair. The clamp is the SPD
    certificate: with ``b σ σ' < a`` every eigenvalue of
    ``a D_x^{-1} + b S ⊗ S'`` is positive (§9.2). ``outer=True``
    broadcasts [Bi] row factors against [Bj] column factors to [Bi, Bj]
    scalars (Gram-tile execution).

    ``spd_margin`` may be a traced scalar (resolved at trace time, so a
    margin override reaches already-jitted entry points as an ARGUMENT
    instead of a module-global monkeypatch that cached traces would
    ignore). None = the module default. A NEGATIVE margin is the
    certificate-FAILURE injection seam of the fault harness
    (distributed/faults.py, DESIGN.md §10): the clamp is bypassed and
    ``b = |margin| · a / (σ σ')`` is forced outright — ``|margin| >= 1``
    makes ``M^{-1}`` indefinite, which the PCG guards must catch as a
    (r, M^{-1} r) < 0 breakdown."""
    vm1, em1, s1 = f1.vmean, f1.emean, f1.sigma
    if outer:
        vm1, em1, s1 = vm1[..., None], em1[..., None], s1[..., None]
    vbar = jnp.maximum(vertex_kernel(vm1, f2.vmean), _VBAR_FLOOR)
    kbar = jnp.maximum(edge_kernel(em1, f2.emean), 0.0)
    a = vbar
    margin = jnp.asarray(SPD_MARGIN if spd_margin is None else spd_margin)
    cap = jnp.abs(margin) * a / jnp.maximum(s1 * f2.sigma, _SIGMA_FLOOR)
    b = jnp.where(margin < 0, cap, jnp.minimum(vbar * vbar * kbar, cap))
    return a, b


def _check_rank(rank: int) -> None:
    if rank not in (1, 2):
        raise ValueError(f"kron_rank must be 1 or 2, got {rank}")


def kron_apply(f1: KronFactors, f2: KronFactors, vertex_kernel,
               edge_kernel, shape: tuple[int, int, int], *,
               rank: int = 2, spd_margin: float | None = None):
    """``apply(r) -> M^{-1} r`` over a per-pair batch: ``f1``/``f2`` are
    stacked [B]-leading factors aligned with the pair batch, ``r`` is
    the [B, n*m] residual. rank=1 keeps only the diagonal Kronecker term
    (mean-field Jacobi — the ablation arm); rank=2 adds the
    ``S ⊗ S'`` sandwich: one batched ``[n,n] @ X @ [m,m]`` contraction
    per application."""
    _check_rank(rank)
    B, n, m = shape
    a, b = kron_scalars(f1, f2, vertex_kernel, edge_kernel,
                        spd_margin=spd_margin)
    dd = f1.dinv[:, :, None] * f2.dinv[:, None, :]          # [B, n, m]

    def apply(r):
        X = r.reshape(B, n, m)
        Y = a[:, None, None] * (dd * X)
        if rank >= 2:
            Y = Y + b[:, None, None] * jnp.einsum(
                "bij,bjk,blk->bil", f1.s, X, f2.s,
                precision=jax.lax.Precision.HIGHEST)
        return Y.reshape(B, n * m)

    return apply


def kron_apply_gram(f1: KronFactors, f2: KronFactors, vertex_kernel,
                    edge_kernel, shape: tuple[int, int, int, int], *,
                    rank: int = 2, spd_margin: float | None = None):
    """Gram-tile variant: PER-AXIS factors ([Bi] row graphs / [Bj]
    column graphs, mirroring the per-axis packs of ``stacked_axis``),
    applied to the row-major pair-flattened [Bi*Bj, n*m] residual. Each
    axis's ``S`` factor exists once and the einsum contracts it against
    all partners — the factor analog of the Gram-tile kernel's
    cross-pair panel reuse."""
    _check_rank(rank)
    Bi, Bj, n, m = shape
    a, b = kron_scalars(f1, f2, vertex_kernel, edge_kernel,
                        spd_margin=spd_margin, outer=True)   # [Bi, Bj]
    dd = f1.dinv[:, None, :, None] * f2.dinv[None, :, None, :]

    def apply(r):
        X = r.reshape(Bi, Bj, n, m)
        Y = a[..., None, None] * (dd * X)
        if rank >= 2:
            Y = Y + b[..., None, None] * jnp.einsum(
                "pij,pqjk,qlk->pqil", f1.s, X, f2.s,
                precision=jax.lax.Precision.HIGHEST)
        return Y.reshape(Bi * Bj, n * m)

    return apply
