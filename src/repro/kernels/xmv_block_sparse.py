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

Each octile pair is contracted on the vector unit: the kernel
regenerates the product weights from ``kappa_e`` and works on a
lane-flattened partner tile (:func:`_vpu_contrib`), for any edge kernel.

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
axis. The per-pair kernels (:func:`xmv_row_panel`,
:func:`xmv_row_panel_batched`) serve blocks that are not Gram-tile
rectangles and buckets over the Gram-tile kernel's VMEM envelope.

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

__all__ = ["RowPanelPack", "pack_row_panels", "pack_graph_row_panels",
           "xmv_row_panel", "xmv_row_panel_batched", "xmv_gram_tile",
           "gram_tile_vmem_bytes", "to_tiles", "from_tiles"]


class RowPanelPack(NamedTuple):
    """Row-panel octile storage for one graph: tiles contiguous per row.

    values_adj/values_lab: [nt, k_max, t, t]; row i's real tiles occupy
      slots [0, count[i]) in COO column order, the rest are zero.
    col:   [nt, k_max] int32 tile-column (P block index) per slot.
    count: [nt] int32 *actual* tiles in each row (the SMEM loop bound).

    Stacked packs (``ops.stack_row_panel_packs``) carry a leading [B]
    axis on every field and feed :func:`xmv_row_panel_batched`. There is
    no slot indirection: the panel layout IS the schedule, so the Pallas
    pipeline stages a whole tile row into VMEM as one block and the
    kernel reuses it across all slot pairs.

    VMEM envelope: the row-panel kernels also keep the pair's whole P
    panel resident (4*n*m*t bytes lane-replicated, fetched once per pair
    and reused by every output block), plus the two row panels
    (2*k_max*t^2 values each). Nothing streams P in row strips yet, so a
    bucket whose P panel does not fit VMEM has no sparse kernel.
    """
    values_adj: jnp.ndarray
    values_lab: jnp.ndarray
    col: jnp.ndarray
    count: jnp.ndarray

    @property
    def tile(self) -> int:
        return self.values_adj.shape[-1]

    @property
    def n_tile_rows(self) -> int:
        return self.col.shape[-2]

    @property
    def k_max(self) -> int:
        return self.col.shape[-1]


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


def resolve_pack_dtype(pack_dtype):
    """Normalize the ``pack_dtype`` knob to a numpy dtype (None -> f32;
    "bfloat16" strings resolve through jax's ml_dtypes registration)."""
    if pack_dtype is None:
        return np.dtype(np.float32)
    if isinstance(pack_dtype, str) and pack_dtype == "bfloat16":
        return np.dtype(jnp.bfloat16)
    return np.dtype(pack_dtype)


def pack_row_panels(oset: OctileSet, k_max: int | None = None,
                    as_numpy: bool = False,
                    pack_dtype=None) -> RowPanelPack:
    """Host-side: lay an OctileSet out as contiguous VMEM-ready row panels.

    ``as_numpy`` keeps the fields as host arrays (for caching layers that
    re-pad and stack before the single device transfer).

    ``pack_dtype`` stores the VALUE buffers (``values_adj`` /
    ``values_lab``) in a narrower dtype — ``jnp.bfloat16`` halves the
    HBM bytes every matvec streams while the kernels keep f32
    accumulators (operands are upcast in VMEM before compute; DESIGN.md
    §9.4). Index/count arrays stay int32. f32 packing is bit-exact.
    """
    dtype = resolve_pack_dtype(pack_dtype)
    t, nt = oset.tile, oset.n_tiles_side
    real = oset.coords[:, 0] >= 0
    rows = oset.coords[real, 0].astype(np.int64)
    cols = oset.coords[real, 1]
    counts, pos = _row_positions(rows, nt)
    if k_max is None:
        k_max = max(int(counts.max(initial=0)), 1)
    elif counts.max(initial=0) > k_max:
        raise ValueError(f"k_max={k_max} < max tiles per row {counts.max()}")
    va = np.zeros((nt, k_max, t, t), dtype)
    ve = np.zeros((nt, k_max, t, t), dtype)
    col = np.zeros((nt, k_max), np.int32)
    va[rows, pos] = oset.values_adj[real].astype(dtype)
    ve[rows, pos] = oset.values_lab[real].astype(dtype)
    col[rows, pos] = cols
    dev = (lambda x: x) if as_numpy else jnp.asarray
    return RowPanelPack(values_adj=dev(va), values_lab=dev(ve),
                        col=dev(col), count=dev(counts.astype(np.int32)))


def pack_graph_row_panels(adjacency, edge_labels=None, tile: int = 8,
                          k_max: int | None = None,
                          pack_dtype=None) -> RowPanelPack:
    """Convenience: dense matrix -> RowPanelPack."""
    return pack_row_panels(
        octile_decompose(np.asarray(adjacency),
                         None if edge_labels is None
                         else np.asarray(edge_labels), tile=tile),
        k_max=k_max, pack_dtype=pack_dtype)


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


def _xmv_kernel(col1, cnt1, col2, cnt2,   # scalar-prefetch refs (SMEM)
                *refs, edge_kernel, acc_dtype, fused, cross, tile, dims,
                with_theta):
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
            a = a1_ref[0, 0, kk].astype(acc_dtype)
            e = e1_ref[0, 0, kk].astype(acc_dtype)

            def inner(kkp, acc):
                prep = p_ref[plead + (ca, col2[prow * kb + kkp])]
                prep = prep.astype(acc_dtype)
                part = part_ref[0, ipl, kkp].astype(acc_dtype)
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


def _operands(packs1: RowPanelPack, packs2: RowPanelPack):
    """(row-graph operands, partner operands) in kernel layout.

    Row graph: ``[B, nt, ka, t, t]`` adjacency + label tiles. Partner:
    one ``[2, t*t]`` row of lane-flattened tiles per slot,
    ``[B, mt, kb, 2, t*t]``."""
    t = packs1.tile
    row = [packs1.values_adj, packs1.values_lab]
    part = jnp.stack([packs2.values_adj, packs2.values_lab], axis=-3)
    return row, part.reshape(part.shape[:-2] + (t * t,))


def _xmv_call(packs1, packs2, P, edge_kernel, diag, interpret, acc_dtype,
              cross, theta=None):
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
    row_ops, part = _operands(packs1, packs2)
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
    with_theta = theta is not None
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
                          acc_dtype=acc_dtype, fused=fused, cross=cross,
                          tile=t, dims=(nt, mt, ka, kb),
                          with_theta=with_theta),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(want, P.dtype),
        interpret=interpret,
        name="xmv_gram_tile" if cross else "xmv_row_panel",
    )(flat(packs1.col), flat(packs1.count), flat(packs2.col),
      flat(packs2.count), *inputs)


def _with_batch(pack: RowPanelPack) -> RowPanelPack:
    return RowPanelPack(*(f[None] for f in pack))


@functools.partial(jax.jit, static_argnames=("edge_kernel", "interpret",
                                             "acc_dtype"))
def xmv_row_panel(pack1: RowPanelPack, pack2: RowPanelPack, P, edge_kernel,
                  *, diag=None, interpret=None, acc_dtype=jnp.float32,
                  theta=None):
    """y = (A (x) A' .* E (x)k E') P via VMEM-staged row panels (one pair).

    ``P`` (and the result) are tile-major ``[nt, mt, t, t]``
    (:func:`to_tiles`).

    With ``diag`` (tile-major like P) the kernel instead returns the
    fused CG operator application ``diag * P - y``. ``theta`` ([P_theta]
    f32, ``pack_theta`` order) overrides the edge kernel's
    hyperparameters with traced values (DESIGN.md §7).
    """
    out = _xmv_call(_with_batch(pack1), _with_batch(pack2), P[None],
                    edge_kernel, None if diag is None else diag[None],
                    interpret, acc_dtype, cross=False, theta=theta)
    return out[0]


@functools.partial(jax.jit, static_argnames=("edge_kernel", "interpret",
                                             "acc_dtype"))
def xmv_row_panel_batched(packs1: RowPanelPack, packs2: RowPanelPack, P,
                          edge_kernel, *, diag=None, interpret=None,
                          acc_dtype=jnp.float32, theta=None):
    """Whole-bucket row-panel block-sparse XMV in ONE ``pallas_call``.

    ``packs1``/``packs2`` are stacked RowPanelPacks
    (``ops.stack_row_panel_packs``) with a leading [B] axis on every
    field; ``P`` is tile-major ``[B, nt, mt, t, t]``. Grid (B, nt, mt):
    the pair axis is the outermost grid dimension, each output tile is
    owned by one grid step, and the (slot, slot') reduction runs
    in-kernel over the VMEM-staged tile rows.

    With ``diag`` (``[B, nt, mt, t, t]``) the fused epilogue emits
    ``diag * P - y``; ``theta`` (shared across the bucket) as in
    :func:`xmv_row_panel`.
    """
    return _xmv_call(packs1, packs2, P, edge_kernel, diag, interpret,
                     acc_dtype, cross=False, theta=theta)


def gram_tile_vmem_bytes(packs_i: RowPanelPack,
                         packs_j: RowPanelPack) -> int:
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
    pack_bytes = np.dtype(packs_i.values_adj.dtype).itemsize
    # two planes per octile: adjacency and labels
    operands = 2 * (ka * t * t           # graph i's tile row
                    + mt * kb * t * t)   # graph j's whole pack
    fp32 = (n * m * t                    # the pair's replicated P panel
            + 2 * t * m)                 # diag + out strips
    return 2 * (pack_bytes * operands + 4 * fp32)  # double buffered


@functools.partial(jax.jit, static_argnames=("edge_kernel", "interpret",
                                             "acc_dtype"))
def xmv_gram_tile(packs_i: RowPanelPack, packs_j: RowPanelPack, P,
                  edge_kernel, *, diag=None, interpret=None,
                  acc_dtype=jnp.float32, theta=None):
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

    ``diag``/``theta`` as in :func:`xmv_row_panel_batched` (``diag``:
    tile-major like P, fused CG epilogue; ``theta``: traced
    hyperparameter vector).
    """
    if P.ndim != 6:
        raise ValueError(
            f"P must be tile-major [Bi, Bj, nt, mt, t, t], got {P.shape}")
    return _xmv_call(packs_i, packs_j, P, edge_kernel, diag, interpret,
                     acc_dtype, cross=True, theta=theta)
