"""Block-sparse on-the-fly Kronecker XMV over non-empty octiles.

The TPU port of the paper's inter-tile sparsity exploitation (Sec. IV-A):
only non-empty octiles participate. The CUDA kernel streams a COO tile list
per warp, stages the streamed tiles in *shared memory* so every warp lane
reuses them, and resolves output collisions with atomics; TPUs have neither
warps nor atomics, so (DESIGN.md §2):

* the COO list is re-bucketed BY TILE ROW at preprocessing time into
  contiguous **row panels** (``pack_row_panels``) — the whole tile row
  (values + columns) lands in VMEM as ONE pipelined block fetch and is
  reused across every slot pair of the output block, the TPU analog of
  the paper's warp-shared tiles;
* the grid is (pair, tile_row_i, tile_row_i'): each output tile is
  owned by exactly one grid step, so accumulation is race-free by
  construction (no atomics needed) and the (slot, slot') reduction runs
  as an in-kernel ``fori_loop`` whose trip counts are the row's *actual*
  slot counts, prefetched to SMEM — padding slots cost a skipped loop
  iteration, not a full grid step (the warp's COO cursor, DESIGN.md §3);
* the *dynamic* tile-column indirection uses scalar prefetch
  (PrefetchScalarGridSpec): the column/count arrays are prefetched to
  SMEM and drive dynamic P-block loads inside the kernel.

Two compute modes per octile pair (paper Sec. IV-B's density-adaptive
primitive choice, re-targeted to the TPU's two compute units):

* **elementwise (VPU)** — regenerate the product weights from
  ``kappa_e`` and contract on the vector unit, 2-D work on a
  lane-flattened partner tile (:func:`_vpu_contrib`); works for any
  edge kernel.
* **MXU low-rank contraction** — for edge kernels with a feature
  expansion ``kappa(x, y) = sum_r f_r(x) f_r(y)``, the pack precomputes
  per-octile weighted tiles ``w_r = a ∘ f_r(e)`` and each octile pair
  contracts as ``sum_r w_r @ P_blk @ w'_r^T`` — one matmul on the
  systolic array plus R elementwise products (:func:`_mxu_contrib`).

Layout (DESIGN.md §2): P, ``diag`` and the result are TILE-MAJOR,
``[.., n/t, m/t, t, t]`` (:func:`to_tiles`), so every tile is selected
by leading-axis indices — what the TPU lowers — and each output tile is
written once by a per-tile ref store.

The paper's SECOND reuse level — "warps across a thread block can
further share tiles via the shared memory" — maps to the **Gram-tile**
kernel (:func:`xmv_gram_tile`, DESIGN.md §8): one row-panel pack per
AXIS of an I x J Gram tile (Bi row-graph packs + Bj column-graph packs,
not Bi*Bj pair packs), a (Bi, nt, Bj) grid whose inner pair axis reuses
graph i's VMEM-staged tile row across all Bj partners, and an in-kernel
output-tile-column loop that collapses the per-pair kernel's mt grid
axis.

Legacy launch granularities kept as benchmark baselines (DESIGN.md §3):

* :func:`xmv_block_sparse` — one pair per ``pallas_call``, unrolled
  (nt, mt, ka, kb) grid;
* :func:`xmv_block_sparse_batched` — whole bucket per ``pallas_call``,
  (B, nt, mt, ka, kb) grid: every (slot, slot') pair is a separate grid
  step that re-fetches its octiles.

These take node-major ``[n, m]`` P with t x t blocks inside the lane
axis, which the TPU compiler refuses: they run in interpret mode only.

All entry points support a **fused diagonal epilogue**: pass
``diag = D_x V_x^{-1}`` (laid out like P) and the kernel emits the full
CG operator application ``diag * p - y`` in the output tile — no extra
XLA op or HBM round-trip per CG iteration (DESIGN.md §3).

Intra-tile sparsity (Sec. IV-B, bitmap compaction) lives at the storage
level: HBM holds only packed non-empty tiles; the kernel computes on dense
t x t blocks after VMEM expansion, mirroring the paper's "stored compact,
expanded in shared memory".
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.octile import OctileSet, octile_decompose

__all__ = ["TilePack", "pack_octiles", "xmv_block_sparse",
           "xmv_block_sparse_batched", "RowPanelPack", "pack_row_panels",
           "pack_graph_row_panels", "xmv_row_panel",
           "xmv_row_panel_batched", "xmv_gram_tile",
           "gram_tile_vmem_bytes", "device_weighted_pack", "to_tiles",
           "from_tiles"]


class TilePack(NamedTuple):
    """Device-side row-bucketed octile storage for one graph.

    values_adj/values_lab: [K+1, t, t] packed non-empty tiles; slot K is
      all-zero (the padding target).
    slot: [n_tile_rows, k_max] int32 -> index into values_*.
    col:  [n_tile_rows, k_max] int32 tile-column (P block index).
    values_grad: optional [K+1, P, R, t, t] per-parameter feature-
      derivative operands ``a ∘ ∂f_r(e)/∂θ`` (``pack_octiles`` with an
      expandable ``edge_kernel``), the adjoint-solve companion buffer
      (DESIGN.md §7). The legacy kernels below never read it; it exists
      so cached TilePacks can be converted to gradient-ready row-panel
      layouts without re-decomposing.

    Stacked packs (``ops.stack_packs``) carry a leading [B] axis on every
    field and feed :func:`xmv_block_sparse_batched`. This is the storage
    of the *legacy* unrolled-grid kernels; the row-panel kernels read the
    contiguous :class:`RowPanelPack` layout instead.
    """
    values_adj: jnp.ndarray
    values_lab: jnp.ndarray
    slot: jnp.ndarray
    col: jnp.ndarray
    values_grad: jnp.ndarray | None = None

    @property
    def tile(self) -> int:
        return self.values_adj.shape[-1]

    @property
    def n_tile_rows(self) -> int:
        return self.slot.shape[-2]


class RowPanelPack(NamedTuple):
    """Row-panel octile storage for one graph: tiles contiguous per row.

    values_adj/values_lab: [nt, k_max, t, t]; row i's real tiles occupy
      slots [0, count[i]) in COO column order, the rest are zero.
    values_w: [nt, k_max, R, t, t] precomputed MXU operands
      ``w_r = a ∘ f_r(e)`` when the pack was built with a
      feature-expandable edge kernel, else None.
    col:   [nt, k_max] int32 tile-column (P block index) per slot.
    count: [nt] int32 *actual* tiles in each row (the SMEM loop bound).
    values_grad: optional [nt, k_max, P, R, t, t] per-parameter
      derivative operands ``wg_r = a ∘ ∂f_r(e)/∂θ_p``
      (``pack_row_panels(..., with_grad=True)``; P indexes
      ``edge_kernel.param_names()``). The adjoint edge-gradient
      contraction runs the SAME MXU kernel at rank 2R with the slot
      operands ``[wg ; w]`` vs ``[w' ; wg']`` (DESIGN.md §7) — exact
      edge-kernel gradients with A's sparsity, never densified.

    Stacked packs (``ops.stack_row_panel_packs``) carry a leading [B]
    axis on every field and feed :func:`xmv_row_panel_batched`. Unlike
    :class:`TilePack` there is no slot indirection: the panel layout IS
    the schedule, so the Pallas pipeline stages a whole tile row into
    VMEM as one block and the kernel reuses it across all slot pairs.

    VMEM envelope: the row-panel kernels also keep the pair's whole P
    panel resident (4*n*m bytes, fetched once per pair and reused by
    every output block), plus the two row panels
    (4*k_max*(2 or R)*t^2 bytes each). Graph-kernel buckets are far
    below the ~16 MB/core budget (n = m = 512 => 1 MB for P); buckets
    beyond n*m ~ 2M elements should fall back to the legacy
    :func:`xmv_block_sparse_batched`, whose P BlockSpec streams t x t
    blocks via prefetch-indexed maps instead.
    """
    values_adj: jnp.ndarray
    values_lab: jnp.ndarray
    values_w: jnp.ndarray | None
    col: jnp.ndarray
    count: jnp.ndarray
    values_grad: jnp.ndarray | None = None

    @property
    def tile(self) -> int:
        return self.values_adj.shape[-1]

    @property
    def n_tile_rows(self) -> int:
        return self.col.shape[-2]

    @property
    def k_max(self) -> int:
        return self.col.shape[-1]

    @property
    def rank(self) -> int | None:
        return None if self.values_w is None else self.values_w.shape[-3]


def _row_positions(rows: np.ndarray, nt: int) -> tuple[np.ndarray,
                                                       np.ndarray]:
    """Per-row slot position of each (row-major sorted) COO entry.

    Returns (counts[nt], pos[K]); vectorized replacement for the
    per-tile Python fill loop (runs once per graph per Gram block).
    """
    K = rows.shape[0]
    counts = np.bincount(rows, minlength=nt) if K else np.zeros(nt,
                                                                np.int64)
    starts = np.zeros(nt + 1, np.int64)
    starts[1:] = np.cumsum(counts)
    pos = np.arange(K, dtype=np.int64) - starts[rows]
    return counts, pos


def pack_octiles(oset: OctileSet, k_max: int | None = None,
                 edge_kernel=None) -> TilePack:
    """Host-side: bucket an OctileSet's COO list by tile row.

    With a feature-expandable ``edge_kernel`` the pack also carries the
    per-parameter ``values_grad`` derivative operands (see
    :class:`TilePack`)."""
    t, nt = oset.tile, oset.n_tiles_side
    K_total = oset.coords.shape[0]       # includes padded() slots, if any
    real = oset.coords[:, 0] >= 0        # padded() marks pad slots with -1
    K = int(real.sum())
    rows = oset.coords[:K, 0].astype(np.int64)
    cols = oset.coords[:K, 1]
    counts, pos = _row_positions(rows, nt)
    if k_max is None:
        k_max = max(int(counts.max(initial=0)), 1)
    elif counts.max(initial=0) > k_max:
        raise ValueError(f"k_max={k_max} < max tiles per row {counts.max()}")
    slot = np.full((nt, k_max), K_total, np.int32)   # K_total = zero tile
    col = np.zeros((nt, k_max), np.int32)
    slot[rows, pos] = np.arange(K, dtype=np.int32)
    col[rows, pos] = cols
    vals_a = np.concatenate(
        [oset.values_adj, np.zeros((1, t, t), np.float32)], axis=0)
    vals_e = np.concatenate(
        [oset.values_lab, np.zeros((1, t, t), np.float32)], axis=0)
    vg = None
    if edge_kernel is not None and edge_kernel.feature_rank() is not None \
            and edge_kernel.param_names():
        from repro.core.octile import feature_operands
        _, wg = feature_operands(vals_a, vals_e, edge_kernel,
                                 with_grad=True)   # [K+1, P, R, t, t]
        vg = jnp.asarray(np.asarray(wg, np.float32))
    return TilePack(values_adj=jnp.asarray(vals_a),
                    values_lab=jnp.asarray(vals_e),
                    slot=jnp.asarray(slot), col=jnp.asarray(col),
                    values_grad=vg)


def resolve_pack_dtype(pack_dtype):
    """Normalize the ``pack_dtype`` knob to a numpy dtype (None -> f32;
    "bfloat16" strings resolve through jax's ml_dtypes registration)."""
    if pack_dtype is None:
        return np.dtype(np.float32)
    if isinstance(pack_dtype, str) and pack_dtype == "bfloat16":
        return np.dtype(jnp.bfloat16)
    return np.dtype(pack_dtype)


def pack_row_panels(oset: OctileSet, edge_kernel=None,
                    k_max: int | None = None,
                    as_numpy: bool = False,
                    with_grad: bool = False,
                    pack_dtype=None) -> RowPanelPack:
    """Host-side: lay an OctileSet out as contiguous VMEM-ready row panels.

    With ``edge_kernel`` carrying a feature expansion
    (``feature_rank() is not None``), the pack also precomputes the MXU
    operands ``w_r = a ∘ f_r(e)`` per octile — loop-invariant across the
    whole CG solve, so weighting at pack time amortizes it over every
    matvec (the same trade the dense low-rank path makes in
    ``core/mgk.py``). ``with_grad`` additionally fills ``values_grad``
    with the per-parameter derivative operands ``a ∘ ∂f_r(e)/∂θ`` —
    loop-invariant across the adjoint contraction the same way
    (DESIGN.md §7).

    ``as_numpy`` keeps the fields as host arrays (for caching layers that
    re-pad and stack before the single device transfer).

    ``pack_dtype`` stores the VALUE buffers (``values_adj`` /
    ``values_lab`` / ``values_w`` / ``values_grad``) in a narrower
    dtype — ``jnp.bfloat16`` halves the HBM bytes every matvec streams
    while the kernels keep f32 accumulators (operands are upcast in
    VMEM before compute; DESIGN.md §9.4). Index/count arrays stay
    int32. f32 packing is bit-exact as before.
    """
    dtype = resolve_pack_dtype(pack_dtype)
    t, nt = oset.tile, oset.n_tiles_side
    real = oset.coords[:, 0] >= 0
    rows = oset.coords[real, 0].astype(np.int64)
    cols = oset.coords[real, 1]
    vals_a = oset.values_adj[real]
    vals_e = oset.values_lab[real]
    counts, pos = _row_positions(rows, nt)
    if k_max is None:
        k_max = max(int(counts.max(initial=0)), 1)
    elif counts.max(initial=0) > k_max:
        raise ValueError(f"k_max={k_max} < max tiles per row {counts.max()}")
    va = np.zeros((nt, k_max, t, t), dtype)
    ve = np.zeros((nt, k_max, t, t), dtype)
    col = np.zeros((nt, k_max), np.int32)
    va[rows, pos] = vals_a.astype(dtype)
    ve[rows, pos] = vals_e.astype(dtype)
    col[rows, pos] = cols
    vw = vg = None
    if edge_kernel is not None and edge_kernel.feature_rank() is not None:
        from repro.core.octile import feature_operands
        with_grad = with_grad and bool(edge_kernel.param_names())
        # operand derivation runs in f32; only the STORED buffers narrow
        w, wg = feature_operands(vals_a, vals_e, edge_kernel,
                                 with_grad=with_grad)
        R = w.shape[-3]
        vw = np.zeros((nt, k_max, R, t, t), dtype)
        vw[rows, pos] = np.asarray(w, np.float32).astype(dtype)
        if wg is not None:
            P = wg.shape[-4]
            vg = np.zeros((nt, k_max, P, R, t, t), dtype)
            vg[rows, pos] = np.asarray(wg, np.float32).astype(dtype)
    dev = (lambda x: x) if as_numpy else jnp.asarray
    opt = lambda x: None if x is None else dev(x)   # noqa: E731
    return RowPanelPack(values_adj=dev(va),
                        values_lab=dev(ve),
                        values_w=opt(vw),
                        col=dev(col),
                        count=dev(counts.astype(np.int32)),
                        values_grad=opt(vg))


def pack_graph(adjacency, edge_labels=None, tile: int = 8,
               k_max: int | None = None) -> TilePack:
    """Convenience: dense matrix -> TilePack."""
    return pack_octiles(octile_decompose(np.asarray(adjacency),
                                         None if edge_labels is None
                                         else np.asarray(edge_labels),
                                         tile=tile), k_max=k_max)


def pack_graph_row_panels(adjacency, edge_labels=None, tile: int = 8,
                          edge_kernel=None, k_max: int | None = None,
                          with_grad: bool = False,
                          pack_dtype=None) -> RowPanelPack:
    """Convenience: dense matrix -> RowPanelPack."""
    return pack_row_panels(
        octile_decompose(np.asarray(adjacency),
                         None if edge_labels is None
                         else np.asarray(edge_labels), tile=tile),
        edge_kernel=edge_kernel, k_max=k_max, with_grad=with_grad,
        pack_dtype=pack_dtype)


def device_weighted_pack(pack: RowPanelPack, edge_kernel, theta=None,
                         with_grad: bool = False) -> RowPanelPack:
    """Recompute a pack's weighted operands ON DEVICE from its structural
    fields: ``values_w = a ∘ f_r(e; theta)`` (and ``values_grad`` when
    ``with_grad``). Works on per-graph and stacked ([B]-leading) packs.

    This is how traced hyperparameters reach the MXU contraction mode,
    whose kernel consumes pre-weighted tiles as plain data: the pack-time
    host precompute bakes the kernel's static parameter values in, so the
    differentiable path re-derives the operands from ``values_lab`` once
    per solve — O(nnz·R) work amortized over every CG iteration, leaving
    the Pallas kernel untouched (DESIGN.md §7). bf16-stored packs
    (``pack_dtype``) upcast before derivation so the feature math and
    the resulting operands stay f32."""
    from repro.core.octile import feature_operands
    w, wg = feature_operands(pack.values_adj.astype(jnp.float32),
                             pack.values_lab.astype(jnp.float32),
                             edge_kernel, theta=theta,
                             with_grad=with_grad)
    return pack._replace(values_w=w, values_grad=wg)


_HIGHEST = jax.lax.Precision.HIGHEST


def to_tiles(x, tile: int):
    """Node-major ``[..., n, m]`` -> tile-major ``[..., n/t, m/t, t, t]``.

    Every kernel of this module reads and writes the product-space
    vector P in this layout: a t x t tile is then addressed by indices
    on LEADING (untiled) axes, which the TPU lowers to plain dynamic
    offsets, while a tile column picked inside the lane axis of an
    ``[n, m]`` array is not lowerable (DESIGN.md §2)."""
    *lead, n, m = x.shape
    if n % tile or m % tile:
        raise ValueError(f"shape {x.shape} is not a multiple of tile={tile}")
    x = x.reshape(*lead, n // tile, tile, m // tile, tile)
    return jnp.swapaxes(x, -3, -2)


def from_tiles(x):
    """Inverse of :func:`to_tiles`: ``[..., nt, mt, t, t]`` -> node-major."""
    *lead, nt, mt, t, t2 = x.shape
    return jnp.swapaxes(x, -3, -2).reshape(*lead, nt * t, mt * t2)


def _lane_replicated(P):
    """``[..., t, t]`` tiles -> ``[..., t, t*t]`` with lane ``k*t + l``
    holding ``P[..., j, l]``: each P row repeated once per partner row
    k, so one VPU op covers all (k, l) of a tile pair (see
    :func:`_vpu_contrib`). Built once per matvec, outside the kernel."""
    t = P.shape[-1]
    return jnp.tile(P, (1,) * (P.ndim - 1) + (t,))


def _fold_lanes(acc, t: int):
    """``[t, t*t]`` accumulator -> ``[t, t]`` tile: sums the t lanes
    ``k*t .. k*t + t-1`` into column k with one 0/1 matmul."""
    tt = t * t
    rows = jax.lax.broadcasted_iota(jnp.int32, (tt, t), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (tt, t), 1)
    fold = (rows // t == cols).astype(acc.dtype)
    return jax.lax.dot_general(acc, fold, (((1,), (0,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=acc.dtype)


def _vpu_contrib(acc, a, e, part, prep, edge_kernel, theta):
    """One octile pair on the vector unit, accumulated lane-flattened.

    a, e: ``[t, t]`` row-graph tiles (rows i, cols j); part: ``[2, t*t]``
    partner tiles flattened to one row each (adjacency, labels; lane
    ``k*t + l``); prep: ``[t, t*t]`` lane-replicated P tile. Adds
    ``a[i,j] a'[k,l] kappa(e[i,j], e'[k,l]) P[j,l]`` at ``acc[i, k*t+l]``
    for every j — 2-D work only (a column of a/e broadcast against a
    partner row); :func:`_fold_lanes` sums l at the end of the tile."""
    apf, epf = part[0:1], part[1:2]
    for j in range(a.shape[1]):
        ej = e[:, j:j + 1]
        kappa = edge_kernel(ej, epf) if theta is None \
            else edge_kernel.apply(ej, epf, theta)
        acc = acc + (a[:, j:j + 1] * apf) * kappa.astype(acc.dtype) \
            * prep[j:j + 1, :]
    return acc


def _mxu_contrib(acc, w, part, prep):
    """One octile pair as the low-rank contraction
    ``sum_r w_r @ P @ w'_r^T``, accumulated lane-flattened.

    w: ``[R*t, t]`` the row graph's weighted tiles stacked over r; part:
    ``[R, t*t]`` the partner's weighted tiles flattened per r; prep:
    ``[t, t*t]`` lane-replicated P tile. One matmul gives every
    ``(w_r @ P)[i, l]`` replicated over k; the partner factor is then an
    elementwise product per r."""
    t = w.shape[1]
    tmp = jax.lax.dot_general(w, prep, (((1,), (0,)), ((), ())),
                              precision=_HIGHEST,
                              preferred_element_type=acc.dtype)
    for r in range(part.shape[0]):
        acc = acc + tmp[r * t:(r + 1) * t] * part[r:r + 1]
    return acc


def _xmv_kernel(col1, cnt1, col2, cnt2,   # scalar-prefetch refs (SMEM)
                *refs, edge_kernel, acc_dtype, fused, mxu, cross, tile,
                dims, with_theta):
    """Shared body of the row-panel and Gram-tile kernels.

    One grid step owns tile row i of one pair and a run of its output
    tiles (i, ip): a single tile for the row-panel grid (pair, i, ip), the
    whole strip ip = 0..mt-1 for the Gram-tile grid (bi, i, bj). The row
    graph's tile row and the partner's panel(s) are VMEM-resident and
    reused across all slot pairs; the (slot, slot') reduction is an
    in-kernel ``fori_loop`` bounded by the SMEM slot counts, so padding
    slots are skipped. Every tile is addressed by leading-axis indices,
    and each output tile is written once by a per-tile ref store.

    ``with_theta``: the first input is the hyperparameter vector in SMEM
    and kappa is regenerated through ``edge_kernel.apply`` — traced
    parameter values reaching a kernel whose edge_kernel is a static jit
    argument (DESIGN.md §7)."""
    t = tile
    nt, mt, ka, kb = dims
    if cross:
        r, i, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        ip0 = 0
    else:
        r = c = pl.program_id(0)
        i, ip0 = pl.program_id(1), pl.program_id(2)
    theta = None
    if with_theta:
        from repro.core.base_kernels import unpack_theta
        t_ref, *refs = refs
        theta = unpack_theta(edge_kernel, t_ref)
    if mxu:
        (w1_ref, part_ref, p_ref), rest = refs[:3], refs[3:]
    else:
        (a1_ref, e1_ref, part_ref, p_ref), rest = refs[:4], refs[4:]
    diag_ref, o_ref = rest if fused else (None, rest[0])
    n_ip = o_ref.shape[-3]
    plead = (0,) * (len(p_ref.shape) - 4)
    olead = (0,) * (len(o_ref.shape) - 3)
    row = r * nt + i

    def out_tile(ipl, carry):
        ip = ip0 + ipl
        prow = c * mt + ip

        def outer(kk, acc):
            ca = col1[row * ka + kk]
            if mxu:
                w = w1_ref[0, 0, kk].astype(acc_dtype)
            else:
                a = a1_ref[0, 0, kk].astype(acc_dtype)
                e = e1_ref[0, 0, kk].astype(acc_dtype)

            def inner(kkp, acc):
                prep = p_ref[plead + (ca, col2[prow * kb + kkp])]
                prep = prep.astype(acc_dtype)
                part = part_ref[0, ipl, kkp].astype(acc_dtype)
                if mxu:
                    return _mxu_contrib(acc, w, part, prep)
                return _vpu_contrib(acc, a, e, part, prep, edge_kernel,
                                    theta)

            return jax.lax.fori_loop(0, cnt2[prow], inner, acc)

        acc = jax.lax.fori_loop(0, cnt1[row], outer,
                                jnp.zeros((t, t * t), acc_dtype))
        y = _fold_lanes(acc, t)
        if fused:
            # the operator application diag*p - y; the P tile is the
            # first t lanes of its VMEM-resident replicated copy
            pblk = p_ref[plead + (i, ip)][:, :t].astype(acc_dtype)
            y = diag_ref[olead + (ipl,)].astype(acc_dtype) * pblk - y
        o_ref[olead + (ipl,)] = y.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, n_ip, out_tile, 0)


def _resolve_mode(mode: str, packs1: RowPanelPack,
                  packs2: RowPanelPack) -> bool:
    """Map the mode knob to the mxu flag, validating pack contents.
    "auto" runs the elementwise body, even on packs that carry the
    weighted tiles: on a TPU v5e it took less device time per Gram-tile
    matvec than the MXU body at every octile edge and rank a workload
    runs. The MXU's one measured lead, at edge 32 with rank 4, has no
    workload behind it; "mxu" takes it explicitly (DESIGN.md §3.4)."""
    if mode in ("auto", "elementwise"):
        return False
    if mode == "mxu":
        if packs1.values_w is None or packs2.values_w is None:
            raise ValueError(
                "mode='mxu' needs packs built with a feature-expandable"
                " edge kernel (pack_row_panels(..., edge_kernel=...))")
        return True
    raise ValueError(f"unknown row-panel mode {mode!r}")


def _operands(packs1: RowPanelPack, packs2: RowPanelPack, mxu: bool):
    """(row-graph operands, partner operands) in kernel layout.

    Row graph: ``[B, nt, ka, t, t]`` adjacency + label tiles (VPU) or the
    weighted tiles stacked over rank, ``[B, nt, ka, R*t, t]`` (MXU).
    Partner: one ``[2 or R, t*t]`` row of lane-flattened tiles per slot,
    ``[B, mt, kb, 2 or R, t*t]``."""
    t = packs1.tile
    if mxu:
        w1 = packs1.values_w
        w2 = packs2.values_w
        row = [w1.reshape(w1.shape[:-3] + (w1.shape[-3] * t, t))]
        part = w2.reshape(w2.shape[:-2] + (t * t,))
    else:
        row = [packs1.values_adj, packs1.values_lab]
        part = jnp.stack([packs2.values_adj, packs2.values_lab], axis=-3)
        part = part.reshape(part.shape[:-2] + (t * t,))
    return row, part


def _xmv_call(packs1, packs2, P, edge_kernel, diag, interpret, acc_dtype,
              mode, cross, theta=None):
    """Shared launcher: ``cross=False`` pairs packs1[b] with packs2[b]
    (P ``[B, nt, mt, t, t]``, grid (B, nt, mt)); ``cross=True`` pairs
    every row graph with every column graph (P ``[Bi, Bj, nt, mt, t, t]``,
    grid (Bi, nt, Bj))."""
    t = packs1.tile
    nt, mt = packs1.n_tile_rows, packs2.n_tile_rows
    ka, kb = packs1.k_max, packs2.k_max
    B1, B2 = packs1.col.shape[0], packs2.col.shape[0]
    pairs = (B1, B2) if cross else (B1,)
    if not cross and B2 != B1:
        raise ValueError(f"pack batches differ: {B1} vs {B2}")
    want = pairs + (nt, mt, t, t)
    if P.shape != want:
        raise ValueError(f"P shape {P.shape} inconsistent with tile packs:"
                         f" expected tile-major {want}")
    if packs2.tile != t:
        raise ValueError(f"tile mismatch: {t} vs {packs2.tile}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    fused = diag is not None
    mxu = _resolve_mode(mode, packs1, packs2)
    if mxu and packs2.rank != packs1.rank:
        raise ValueError(
            f"feature rank mismatch: {packs1.rank} vs {packs2.rank}")
    row_ops, part = _operands(packs1, packs2, mxu)
    tt = t * t

    if cross:
        grid = (B1, nt, B2)
        row_map = lambda bi, i, bj, *_: (bi, i, 0, 0, 0)          # noqa
        part_spec = pl.BlockSpec((1, mt) + part.shape[2:],
                                 lambda bi, i, bj, *_: (bj, 0, 0, 0, 0))
        p_spec = pl.BlockSpec((1, 1, nt, mt, t, tt),
                              lambda bi, i, bj, *_: (bi, bj, 0, 0, 0, 0))
        out_spec = pl.BlockSpec((1, 1, 1, mt, t, t),
                                lambda bi, i, bj, *_: (bi, bj, i, 0, 0, 0))
    else:
        grid = (B1, nt, mt)
        row_map = lambda b, i, ip, *_: (b, i, 0, 0, 0)            # noqa
        part_spec = pl.BlockSpec((1, 1) + part.shape[2:],
                                 lambda b, i, ip, *_: (b, ip, 0, 0, 0))
        p_spec = pl.BlockSpec((1, nt, mt, t, tt),
                              lambda b, i, ip, *_: (b, 0, 0, 0, 0))
        out_spec = pl.BlockSpec((1, 1, 1, t, t),
                                lambda b, i, ip, *_: (b, i, ip, 0, 0))
    in_specs = [pl.BlockSpec((1, 1) + x.shape[2:], row_map)
                for x in row_ops] + [part_spec, p_spec]
    inputs = row_ops + [part, _lane_replicated(P)]
    with_theta = theta is not None and not mxu
    if with_theta:
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        inputs.insert(0, jnp.asarray(theta, jnp.float32).reshape(-1))
    if fused:
        in_specs.append(out_spec)
        inputs.append(diag)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
    )
    # slot tables go to SMEM flattened: a 2-D/3-D SMEM array pads its
    # minor axes and a bucket's tables would overflow the scalar memory
    flat = lambda x: x.reshape(-1).astype(jnp.int32)             # noqa
    return pl.pallas_call(
        functools.partial(_xmv_kernel, edge_kernel=edge_kernel,
                          acc_dtype=acc_dtype, fused=fused, mxu=mxu,
                          cross=cross, tile=t, dims=(nt, mt, ka, kb),
                          with_theta=with_theta),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(want, P.dtype),
        interpret=interpret,
        name="xmv_gram_tile" if cross else "xmv_row_panel",
    )(flat(packs1.col), flat(packs1.count), flat(packs2.col),
      flat(packs2.count), *inputs)


def _with_batch(pack: RowPanelPack) -> RowPanelPack:
    return RowPanelPack(*(None if f is None else f[None] for f in pack))


@functools.partial(jax.jit, static_argnames=("edge_kernel", "interpret",
                                             "acc_dtype", "mode"))
def xmv_row_panel(pack1: RowPanelPack, pack2: RowPanelPack, P, edge_kernel,
                  *, diag=None, mode: str = "auto", interpret=None,
                  acc_dtype=jnp.float32, theta=None):
    """y = (A (x) A' .* E (x)k E') P via VMEM-staged row panels (one pair).

    ``P`` (and the result) are tile-major ``[nt, mt, t, t]``
    (:func:`to_tiles`). ``mode``: "elementwise" (VPU, any edge kernel),
    "mxu" (low-rank contraction; needs packs built with the edge kernel),
    or "auto" (elementwise, the body measured faster on the chip).

    With ``diag`` (tile-major like P) the kernel instead returns the
    fused CG operator application ``diag * P - y``. ``theta`` ([P_theta]
    f32, ``pack_theta`` order) overrides the edge kernel's
    hyperparameters with traced values on the elementwise path; the MXU
    path takes its parameters through ``device_weighted_pack`` instead
    (DESIGN.md §7).
    """
    out = _xmv_call(_with_batch(pack1), _with_batch(pack2), P[None],
                    edge_kernel, None if diag is None else diag[None],
                    interpret, acc_dtype, mode, cross=False, theta=theta)
    return out[0]


@functools.partial(jax.jit, static_argnames=("edge_kernel", "interpret",
                                             "acc_dtype", "mode"))
def xmv_row_panel_batched(packs1: RowPanelPack, packs2: RowPanelPack, P,
                          edge_kernel, *, diag=None, mode: str = "auto",
                          interpret=None, acc_dtype=jnp.float32,
                          theta=None):
    """Whole-bucket row-panel block-sparse XMV in ONE ``pallas_call``.

    ``packs1``/``packs2`` are stacked RowPanelPacks
    (``ops.stack_row_panel_packs``) with a leading [B] axis on every
    field; ``P`` is tile-major ``[B, nt, mt, t, t]``. Grid (B, nt, mt):
    the pair axis is the outermost grid dimension, each output tile is
    owned by one grid step, and the (slot, slot') reduction runs
    in-kernel over the VMEM-staged tile rows (vs a grid step per slot
    pair in the legacy :func:`xmv_block_sparse_batched`).

    With ``diag`` (``[B, nt, mt, t, t]``) the fused epilogue emits
    ``diag * P - y``; ``theta`` (shared across the bucket) as in
    :func:`xmv_row_panel`.
    """
    return _xmv_call(packs1, packs2, P, edge_kernel, diag, interpret,
                     acc_dtype, mode, cross=False, theta=theta)


def gram_tile_vmem_bytes(packs_i: RowPanelPack, packs_j: RowPanelPack,
                         mxu: bool) -> int:
    """Per-grid-step VMEM envelope of :func:`xmv_gram_tile` in bytes
    (x2 for the pipeline's double buffering): graph j's whole
    pack + graph i's tile row + the pair's lane-replicated P panel + the
    diag/out strips. Pack operands are costed at their STORED itemsize —
    bf16 packs (``pack_dtype``) halve the operand share of the envelope,
    which is exactly what lets larger tiles stay on the Gram-tile
    kernel. ``gram_pair_step`` uses this to route over-budget buckets to
    the per-pair :func:`xmv_row_panel_batched`, which stages one partner
    tile row per step instead of the whole pack."""
    t = packs_i.tile
    nt, mt = packs_i.n_tile_rows, packs_j.n_tile_rows
    ka, kb = packs_i.k_max, packs_j.k_max
    n, m = nt * t, mt * t
    ci = packs_i.rank if (mxu and packs_i.rank) else 2
    cj = packs_j.rank if (mxu and packs_j.rank) else 2
    pack_bytes = np.dtype(packs_i.values_adj.dtype).itemsize
    operands = (ka * ci * t * t          # graph i's tile row
                + mt * kb * cj * t * t)  # graph j's whole pack
    fp32 = (n * m * t                    # the pair's replicated P panel
            + 2 * t * m)                 # diag + out strips
    return 2 * (pack_bytes * operands + 4 * fp32)  # double buffered


@functools.partial(jax.jit, static_argnames=("edge_kernel", "interpret",
                                             "acc_dtype", "mode"))
def xmv_gram_tile(packs_i: RowPanelPack, packs_j: RowPanelPack, P,
                  edge_kernel, *, diag=None, mode: str = "auto",
                  interpret=None, acc_dtype=jnp.float32, theta=None):
    """All Bi x Bj cross-pair XMVs of a Gram tile in ONE ``pallas_call``.

    ``packs_i``/``packs_j`` are stacked RowPanelPacks with a leading
    PER-AXIS batch — Bi packs for the row graphs and Bj for the column
    graphs, NOT Bi*Bj per-pair packs, so each graph's panels live in HBM
    exactly once per Gram tile. ``P`` is tile-major
    ``[Bi, Bj, nt, mt, t, t]``; the result is the same-shaped stack of
    y = (A_i (x) A'_j .* E_i (x)k E'_j) P_ij.

    Grid (Bi, nt, Bj): graph i's tile row is fetched once per (bi, i)
    and reused across ALL Bj partners (the pair-axis operand reuse the
    paper gets from thread-block shared memory); graph j's whole
    row-panel pack is staged per step and the output-tile loop over the
    strip runs in-kernel, collapsing the per-pair kernel's mt grid axis.
    VMEM envelope per step: :func:`gram_tile_vmem_bytes`. This function
    does NOT guard the envelope itself; the Gram driver's
    ``gram_pair_step`` checks it and routes over-budget buckets to the
    per-pair :func:`xmv_row_panel_batched`.

    ``mode``/``diag``/``theta`` as in :func:`xmv_row_panel_batched`
    (``diag``: tile-major like P, fused CG epilogue; ``theta``: traced
    hyperparameter vector on the elementwise path).
    """
    if P.ndim != 6:
        raise ValueError(
            f"P must be tile-major [Bi, Bj, nt, mt, t, t], got {P.shape}")
    return _xmv_call(packs_i, packs_j, P, edge_kernel, diag, interpret,
                     acc_dtype, mode, cross=True, theta=theta)


def _kernel(slot_a, col_a, slot_b, col_b,   # scalar-prefetch refs
            *refs, edge_kernel, acc_dtype, fused, batched):
    """Legacy unrolled-grid kernel body (per-pair and batched).

    Grid layout: (nt, mt, ka, kb) per-pair, (B, nt, mt, ka, kb) batched;
    the two trailing dims are the reduction over octile slots, so the
    output block is revisited consecutively and accumulation is race-free.
    Kept as the benchmark baseline for the row-panel kernel above.
    """
    d = 1 if batched else 0
    kk, kkp = pl.program_id(2 + d), pl.program_id(3 + d)
    n_kk, n_kkp = pl.num_programs(2 + d), pl.num_programs(3 + d)
    if fused:
        a_ref, e_ref, ap_ref, ep_ref, p_ref, diag_ref, pe_ref, o_ref = refs
    else:
        a_ref, e_ref, ap_ref, ep_ref, p_ref, o_ref = refs
        diag_ref = pe_ref = None

    @pl.when(jnp.logical_and(kk == 0, kkp == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    if batched:
        a, e = a_ref[0, 0], e_ref[0, 0]
        ap, ep = ap_ref[0, 0], ep_ref[0, 0]
        p = p_ref[0]
    else:
        a, e = a_ref[0], e_ref[0]
        ap, ep = ap_ref[0], ep_ref[0]
        p = p_ref[...]
    t = a.shape[-1]
    # flattening the partner tile in-kernel is fine here: these kernels
    # run in interpret mode only
    part = jnp.stack([ap.reshape(t * t), ep.reshape(t * t)])
    acc = _vpu_contrib(jnp.zeros((t, t * t), acc_dtype),
                       a.astype(acc_dtype), e.astype(acc_dtype),
                       part.astype(acc_dtype),
                       _lane_replicated(p.astype(acc_dtype)), edge_kernel,
                       None)
    contrib = _fold_lanes(acc, t).astype(o_ref.dtype)
    if batched:
        contrib = contrib[None]

    if not fused:
        o_ref[...] += contrib
        return

    acc = o_ref[...] + contrib
    last = jnp.logical_and(kk == n_kk - 1, kkp == n_kkp - 1)

    @pl.when(last)
    def _epilogue():
        # final grid step owns the completed y block: emit diag*p - y
        o_ref[...] = (diag_ref[...] * pe_ref[...]).astype(o_ref.dtype) - acc

    @pl.when(jnp.logical_not(last))
    def _accumulate():
        o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("edge_kernel", "interpret",
                                             "acc_dtype"))
def xmv_block_sparse(pack1: TilePack, pack2: TilePack, P, edge_kernel, *,
                     diag=None, interpret=None, acc_dtype=jnp.float32):
    """y = (A (x) A' .* E (x)k E') P using only non-empty octiles.

    Legacy unrolled-grid kernel: every (slot, slot') pair is a full grid
    step. Superseded by :func:`xmv_row_panel`; kept as the baseline arm
    of the BENCH_xmv comparison and the parity tests.

    With ``diag`` ([n, m]) the kernel instead returns the fused CG operator
    application ``diag * P - y`` (epilogue in the last reduction step).

    Work: O(K1_max_row * K2_max_row * nt * mt * t^4) vs the dense kernel's
    O(n^2 m^2) — the paper's Fig. 9 'Sparse' rung.
    """
    t = pack1.tile
    nt, mt = pack1.n_tile_rows, pack2.n_tile_rows
    ka, kb = pack1.slot.shape[1], pack2.slot.shape[1]
    n, m = P.shape
    if n != nt * t or m != mt * t:
        raise ValueError(f"P shape {P.shape} inconsistent with tile packs"
                         f" ({nt}x{t}, {mt}x{t})")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    fused = diag is not None

    in_specs = [
        pl.BlockSpec((1, t, t),
                     lambda i, ip, kk, kkp, sa, ca, sb, cb:
                     (sa[i, kk], 0, 0)),
        pl.BlockSpec((1, t, t),
                     lambda i, ip, kk, kkp, sa, ca, sb, cb:
                     (sa[i, kk], 0, 0)),
        pl.BlockSpec((1, t, t),
                     lambda i, ip, kk, kkp, sa, ca, sb, cb:
                     (sb[ip, kkp], 0, 0)),
        pl.BlockSpec((1, t, t),
                     lambda i, ip, kk, kkp, sa, ca, sb, cb:
                     (sb[ip, kkp], 0, 0)),
        pl.BlockSpec((t, t),
                     lambda i, ip, kk, kkp, sa, ca, sb, cb:
                     (ca[i, kk], cb[ip, kkp])),
    ]
    inputs = [pack1.values_adj, pack1.values_lab,
              pack2.values_adj, pack2.values_lab, P]
    if fused:
        out_map = lambda i, ip, kk, kkp, sa, ca, sb, cb: (i, ip)  # noqa
        in_specs += [pl.BlockSpec((t, t), out_map),   # diag block
                     pl.BlockSpec((t, t), out_map)]   # P at the OUT block
        inputs += [diag, P]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(nt, mt, ka, kb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (t, t), lambda i, ip, kk, kkp, sa, ca, sb, cb: (i, ip)),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, edge_kernel=edge_kernel,
                          acc_dtype=acc_dtype, fused=fused, batched=False),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, m), P.dtype),
        interpret=interpret,
    )(pack1.slot, pack1.col, pack2.slot, pack2.col, *inputs)
    return out


@functools.partial(jax.jit, static_argnames=("edge_kernel", "interpret",
                                             "acc_dtype"))
def xmv_block_sparse_batched(packs1: TilePack, packs2: TilePack, P,
                             edge_kernel, *, diag=None, interpret=None,
                             acc_dtype=jnp.float32):
    """Whole-bucket block-sparse XMV in ONE ``pallas_call`` (legacy grid).

    ``packs1``/``packs2`` are stacked TilePacks (``ops.stack_packs``) with a
    leading [B] axis on every field; ``P`` is [B, n, m]. The pair axis is
    the outermost grid dimension and the scalar-prefetch index maps select
    per-pair tiles via ``slot[b, i, k]`` — replacing B dispatches (and B
    jit boundaries) per CG iteration with one (paper Sec. V). Every
    (slot, slot') pair is still a separate grid step that re-fetches its
    octiles; :func:`xmv_row_panel_batched` removes that too. Kept as the
    benchmark baseline.

    With ``diag`` ([B, n, m]) the fused epilogue emits ``diag * P - y``.
    """
    B = packs1.values_adj.shape[0]
    t = packs1.values_adj.shape[-1]
    nt, mt = packs1.slot.shape[1], packs2.slot.shape[1]
    ka, kb = packs1.slot.shape[2], packs2.slot.shape[2]
    Bp, n, m = P.shape
    if Bp != B:
        raise ValueError(f"P batch {Bp} != pack batch {B}")
    if n != nt * t or m != mt * t:
        raise ValueError(f"P shape {P.shape} inconsistent with tile packs"
                         f" ({nt}x{t}, {mt}x{t})")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    fused = diag is not None

    in_specs = [
        pl.BlockSpec((1, 1, t, t),
                     lambda b, i, ip, kk, kkp, sa, ca, sb, cb:
                     (b, sa[b, i, kk], 0, 0)),
        pl.BlockSpec((1, 1, t, t),
                     lambda b, i, ip, kk, kkp, sa, ca, sb, cb:
                     (b, sa[b, i, kk], 0, 0)),
        pl.BlockSpec((1, 1, t, t),
                     lambda b, i, ip, kk, kkp, sa, ca, sb, cb:
                     (b, sb[b, ip, kkp], 0, 0)),
        pl.BlockSpec((1, 1, t, t),
                     lambda b, i, ip, kk, kkp, sa, ca, sb, cb:
                     (b, sb[b, ip, kkp], 0, 0)),
        pl.BlockSpec((1, t, t),
                     lambda b, i, ip, kk, kkp, sa, ca, sb, cb:
                     (b, ca[b, i, kk], cb[b, ip, kkp])),
    ]
    inputs = [packs1.values_adj, packs1.values_lab,
              packs2.values_adj, packs2.values_lab, P]
    if fused:
        out_map = lambda b, i, ip, kk, kkp, sa, ca, sb, cb: (b, i, ip)  # noqa
        in_specs += [pl.BlockSpec((1, t, t), out_map),
                     pl.BlockSpec((1, t, t), out_map)]
        inputs += [diag, P]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, nt, mt, ka, kb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, t, t), lambda b, i, ip, kk, kkp, sa, ca, sb, cb: (b, i, ip)),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, edge_kernel=edge_kernel,
                          acc_dtype=acc_dtype, fused=fused, batched=True),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, n, m), P.dtype),
        interpret=interpret,
    )(packs1.slot, packs1.col, packs2.slot, packs2.col, *inputs)
    return out
