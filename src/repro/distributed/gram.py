"""Distributed all-pairs Gram computation.

Two-level parallelism on the production mesh (DESIGN.md §4):

* the PAIR axis (embarrassingly parallel, paper Sec. V-B) shards over every
  non-"model" mesh axis — ("pod", "data") on the multi-pod mesh;
* the MODEL axis parallelizes *within* a pair by sharding graph-1's node
  dimension — the rows of the nm x nm product system. CG dot products then
  reduce over sharded rows; GSPMD inserts the all-reduces (this is the
  collective-bound regime the §Roofline table quantifies).

Fault tolerance: the driver walks a SchedulePlan, persists every finished
PairBlock to a ChunkStore (atomic, CRC, first-writer-wins) and on restart
recomputes only missing blocks. Elasticity: replan() on the remaining
blocks whenever the device count changes between rounds.

Self-healing (DESIGN.md §10.2): every block's solve is health-checked
against the per-pair PCG status flags (core/pcg.py), and an unhealthy
block walks a DEGRADATION LADDER — same-rung retries first (transient
faults recompute clean, preserving bitwise identity with a fault-free
run), then cumulative escalation kron→jacobi preconditioner, bf16→f32
packs, segmented→lockstep PCG, and finally the dense numpy reference
oracle per pair. Pairs still broken after the last rung are QUARANTINED:
dropped from the saved block, listed in the manifest record and in
``GramDriver.health`` — never a silent NaN in the Gram. Chunks whose CRC
fails on restore are quarantined-and-recomputed the same way, and
repeatedly failing buckets are deprioritized on replanning
(distributed/scheduler.py failures knob).
"""
from __future__ import annotations

import contextlib
import dataclasses
import logging
from typing import Callable, Iterable

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.base_kernels import BaseKernel, Constant
from repro.core.graph import GraphBatch
from repro.core.mgk import MGKResult, mgk_pairs, mgk_pairs_sparse
from repro.core.pcg import PCG_BREAKDOWN, PCG_DIVERGENCE, PCG_MAX_ITER, \
    PCG_NONFINITE, PCG_RESTARTED, PCG_STAGNATION
from repro.data.loader import BucketedDataset, PairBlock, pair_blocks
from .checkpoint import ChunkStore
from .scheduler import SchedulePlan, make_plan, replan

logger = logging.getLogger(__name__)

# status bits that flag a pair's solve as UNHEALTHY for the degradation
# ladder: any detected anomaly, including a recovered restart — a
# restarted trajectory differs from the clean one, so the block is
# retried at the same rung to reproduce the fault-free result bit-for-
# bit. MAX_ITER alone is NOT here: a merely-slow pair is surfaced via
# the non-convergence summary, not escalated (escalating it would churn
# without a defect to heal).
_UNHEALTHY = (PCG_BREAKDOWN | PCG_NONFINITE | PCG_STAGNATION
              | PCG_DIVERGENCE | PCG_RESTARTED)

__all__ = ["gram_pair_step", "solve_pair_block", "GramDriver",
           "GraphPackCache", "pair_shardings"]


class GraphPackCache:
    """Per-graph row-panel pack cache for the all-pairs driver.

    A graph appears in O(N) pair blocks of the Gram matrix; without a
    cache it is octile-decomposed and repacked every time its bucket
    shows up (``row_panel_packs_for_batch`` per block). Here each graph
    is decomposed ONCE per (dataset index, pad_to) — keyed by dataset
    index, not array contents — and stored as host arrays at its natural
    slot count; per-block stacking is then a cheap pad-and-stack to the
    block's shared k_max.

    ``edge_kernel`` (feature-expandable) additionally precomputes the MXU
    contraction operands into the cached packs. ``max_entries`` bounds
    host memory with LRU eviction (configurable through
    ``GramDriver.pack_cache_entries``) — the scheduler emits blocks
    bucket-contiguously, so even a bound far below the dataset size keeps
    the reuse (a graph's blocks are temporally close). An evicted graph
    is simply re-decomposed on its next miss; the round trip is
    bit-identical (the pack is a pure function of the graph arrays).

    Pack-time STATISTICS (octile count, nnz, occupancy density) persist
    in ``self.stats`` even after the pack itself is evicted — they are a
    few floats per graph and feed the scheduler's cost model
    (``GramDriver.plan`` -> ``scheduler.estimate_cost``), replacing its
    uniform-density assumption with measured sparsity.

    ``pack_dtype`` stores the pack value buffers (``values_adj`` /
    ``values_lab`` / ``values_w`` / ``values_grad``) in a narrower
    dtype — ``jnp.bfloat16`` halves HBM bytes per matvec while the
    kernels keep f32 accumulators (DESIGN.md §9.4).

    Kronecker-preconditioner FACTORS (``core/precond.py``) are cached
    alongside the packs, keyed by the same (dataset index, pad):
    computed once per graph at pack time from its degree/adjacency
    statistics, stacked per pair batch (:meth:`stacked_factors`) or per
    Gram-tile axis (mirroring :meth:`stacked_axis`). A few O(n²) host
    arrays per graph; evicted and rebuilt with the packs.

    One cache serves several octile edges: packs and their stats are
    keyed by (dataset index, pad, t), ``tile`` is the edge a call uses
    when it names none. Gram-tile steps pick t per block (DESIGN.md
    §3.4) and record in ``edges`` the edge each bucket pair's blocks ran
    at, which :meth:`GramDriver._block_densities` reads the stats at.
    The Kronecker factors do not depend on t.

    Lookups count as ``pack_cache.hit`` / ``pack_cache.miss`` in the
    program's counters (:mod:`repro.obs`); a miss builds under the span
    ``mgk.pack``, stacking and the transfer run under ``mgk.stack``.
    """

    def __init__(self, tile: int = 8, edge_kernel=None,
                 max_entries: int = 65536, with_grad: bool = False,
                 pack_dtype=None):
        import collections
        self.tile = tile
        self.edge_kernel = edge_kernel
        self.max_entries = max_entries
        self.with_grad = with_grad   # also bake values_grad companions
        self.pack_dtype = pack_dtype
        self._packs: "collections.OrderedDict" = collections.OrderedDict()
        self._factors: "collections.OrderedDict" = \
            collections.OrderedDict()
        self.stats: dict = {}        # (idx, pad, t) -> octile/nnz/density
        self.edges: dict = {}        # (pad_row, pad_col) -> t last run

    def _lru_get(self, store, key, build) -> dict:
        """Shared LRU lookup for the pack and factor stores: counts
        hits/misses (both stores feed the same counters), bounds each
        store at ``max_entries``, builds on miss."""
        hit = store.get(key)
        if hit is not None:
            obs.count("pack_cache.hit")
            store.move_to_end(key)
            return hit
        obs.count("pack_cache.miss")
        while len(store) >= self.max_entries:
            store.popitem(last=False)
        with obs.span("mgk.pack"):
            entry = build()
        store[key] = entry
        return entry

    def _pack(self, idx, adjacency, labels, pad_to, tile) -> dict:
        key = (int(idx), int(pad_to), int(tile))
        return self._lru_get(self._packs, key,
                             lambda: self._build_pack(key, adjacency,
                                                      labels))

    def _build_pack(self, key, adjacency, labels) -> dict:
        from repro.core.octile import octile_decompose
        from repro.kernels.xmv_block_sparse import pack_row_panels
        oset = octile_decompose(adjacency, labels, tile=key[2])
        nt = oset.n_tiles_side
        self.stats[key] = {
            "octiles": int(oset.n_nonempty),
            "nnz": int(np.count_nonzero(oset.values_adj)),
            "tile_rows": int(nt),
            "density": float(oset.n_nonempty) / max(nt * nt, 1),
        }
        # as_numpy: the cache re-pads and stacks host-side; the single
        # device transfer happens in stacked()
        p = pack_row_panels(oset, edge_kernel=self.edge_kernel,
                            as_numpy=True, with_grad=self.with_grad,
                            pack_dtype=self.pack_dtype)
        return {f: getattr(p, f) for f in type(p)._fields}

    def _factor(self, idx, batch: GraphBatch, b: int, pad_to) -> dict:
        """Per-graph Kronecker-preconditioner factors, cached like the
        packs (host numpy at the graph's padded size; same LRU bound
        and hit/miss counters, in their own store)."""
        from repro.core.precond import KronFactors, kron_factor_arrays

        def build():
            f = kron_factor_arrays(
                obs.to_host(batch.adjacency[b]),
                obs.to_host(batch.degrees[b]),
                obs.to_host(batch.edge_labels[b]),
                obs.to_host(batch.vertex_labels[b]),
                obs.to_host(batch.node_mask[b]))
            return {name: np.asarray(getattr(f, name))
                    for name in KronFactors._fields}

        return self._lru_get(self._factors, (int(idx), int(pad_to)),
                             build)

    def stacked_factors(self, indices, batch: GraphBatch):
        """Stacked :class:`~repro.core.precond.KronFactors` for one pair
        batch (or, called with the UNIQUE graphs of a Gram-tile axis,
        the per-axis factors — the factor analog of
        :meth:`stacked_axis`). Indexing contract as :meth:`stacked`:
        entries beyond ``len(indices)`` are dummy pairs (index -1)."""
        from repro.core.precond import KronFactors
        B = batch.adjacency.shape[0]
        pad_to = batch.adjacency.shape[1]
        with obs.span("mgk.stack"):
            entries = []
            for b in range(B):
                idx = int(indices[b]) if b < len(indices) else -1
                entries.append(self._factor(idx, batch, b, pad_to))
            return KronFactors(**{
                name: obs.to_device(np.stack([e[name] for e in entries]))
                for name in KronFactors._fields})

    def density(self, idx: int, pad_to: int,
                tile: int | None = None) -> float | None:
        """Measured octile occupancy of graph ``idx`` at bucket pad
        ``pad_to`` and octile edge ``tile`` (default: the cache's) —
        None until the graph has been packed once at that edge."""
        s = self.stats.get((int(idx), int(pad_to), int(tile or self.tile)))
        return None if s is None else s["density"]

    @staticmethod
    def _pad_k(arr: np.ndarray, k_max: int) -> np.ndarray:
        k = arr.shape[1]
        if k == k_max:
            return arr
        pad = [(0, 0)] * arr.ndim
        pad[1] = (0, k_max - k)
        return np.pad(arr, pad)

    def _host_stacked(self, indices, batch: GraphBatch,
                     tile: int | None = None):
        """The stacked RowPanelPack for one (padded) pair batch at octile
        edge ``tile`` (default: the cache's), as host arrays.

        ``indices[b]`` is the dataset index of ``batch`` entry b; entries
        beyond ``len(indices)`` are data-parallel dummy pairs (cached
        under index -1 — their adjacency is all zero)."""
        from repro.kernels.xmv_block_sparse import RowPanelPack
        t = tile or self.tile
        B = batch.adjacency.shape[0]
        pad_to = batch.adjacency.shape[1]
        if pad_to % t:
            raise ValueError(
                f"bucket padded to {pad_to}, not a multiple of"
                f" tile={t}; pad buckets to a multiple of the"
                f" tile edge (loader multiple_of)")
        with obs.span("mgk.stack"):
            entries = []
            for b in range(B):
                idx = int(indices[b]) if b < len(indices) else -1
                entries.append(self._pack(
                    idx, obs.to_host(batch.adjacency[b]),
                    obs.to_host(batch.edge_labels[b]), pad_to, t))
            k_max = max(e["col"].shape[1] for e in entries)

            def stack(field):
                if entries[0][field] is None:
                    return None
                if field == "count":
                    return np.stack([e[field] for e in entries])
                return np.stack(
                    [self._pad_k(e[field], k_max) for e in entries])

            return RowPanelPack(**{f: stack(f)
                                   for f in RowPanelPack._fields})

    @staticmethod
    def send(pack):
        """A host-stacked pack on the device, field by field."""
        with obs.span("mgk.stack"):
            return type(pack)(*(None if x is None else obs.to_device(x)
                                for x in pack))

    def stacked(self, indices, batch: GraphBatch, tile: int | None = None):
        """The stacked RowPanelPack for one (padded) pair batch at
        octile edge ``tile``, on the device (:meth:`_host_stacked`)."""
        return self.send(self._host_stacked(indices, batch, tile))

    def stacked_axis(self, indices, batch: GraphBatch,
                     tile: int | None = None):
        """PER-AXIS pack for Gram-tile execution (DESIGN.md §8): one
        stacked RowPanelPack over the given UNIQUE graphs — the Bi row
        (or Bj column) axis of an I x J Gram tile — as host arrays, so
        the step can size the kernel's VMEM envelope before
        :meth:`send` ships it. Compared to building :meth:`stacked`
        per-pair packs for the tile's flattened pair batch, this skips
        the Bj-fold (resp. Bi-fold) re-stacking and device-transfer
        duplication entirely: each graph's panels are padded and
        shipped once per tile, and the Gram-tile kernel reuses them
        across every partner."""
        if batch.adjacency.shape[0] != len(indices):
            raise ValueError(
                f"axis batch size {batch.adjacency.shape[0]} != "
                f"{len(indices)} axis indices (per-axis packs take the"
                f" UNIQUE graphs, not the flattened pair batch)")
        return self._host_stacked(indices, batch, tile)


def pair_shardings(mesh: Mesh) -> tuple:
    """(in_shardings for (g1, g2), out_shardings for MGKResult).

    g1's node dimension rides the "model" axis (rows of the product
    system); g2 is replicated over "model". The pair/batch axis shards over
    all remaining mesh axes.
    """
    batch_axes = tuple(a for a in mesh.axis_names if a != "model")
    model = "model" if "model" in mesh.axis_names else None
    b = batch_axes if batch_axes else None

    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    g1_shard = GraphBatch(
        adjacency=ns(b, model, None),
        edge_labels=ns(b, model, None),
        vertex_labels=ns(b, model),
        start_prob=ns(b, model),
        stop_prob=ns(b, model),
        degrees=ns(b, model),
        node_mask=ns(b, model),
        n_nodes=ns(b),
    )
    g2_shard = GraphBatch(
        adjacency=ns(b, None, None),
        edge_labels=ns(b, None, None),
        vertex_labels=ns(b, None),
        start_prob=ns(b, None),
        stop_prob=ns(b, None),
        degrees=ns(b, None),
        node_mask=ns(b, None),
        n_nodes=ns(b),
    )
    out_shard = MGKResult(values=ns(b), iterations=ns(b), converged=ns(b),
                          nodal=None, matvec_pairs=ns(), status=ns(b))
    return (g1_shard, g2_shard), out_shard


# per-grid-step VMEM envelope above which gram_pair_step routes a
# Gram-tile block back to the per-pair row-panel kernel (the ~16 MB/core
# budget minus headroom for Mosaic's own buffers)
_GRAM_TILE_VMEM_BUDGET = 12 << 20

# octile edges a Gram-tile block may pack at, largest first: the
# elementwise body pays a fixed cost per slot pair, and a larger edge
# runs fewer, fuller slot pairs (DESIGN.md §3.4)
_GRAM_TILE_EDGES = (32, 16, 8)
# the octile edge of every other sparse route (per-pair blocks, and
# Gram-tile blocks that fit VMEM at no edge)
_DEFAULT_TILE = 8


def _axis_structure(rows, cols):
    """(unique_rows, unique_cols) if (rows, cols) is the row-major
    flattening of their rectangle (``gram_tile_blocks`` structure),
    else None (ragged blocks fall back to per-pair execution)."""
    rows = np.asarray(rows)
    cols = np.asarray(cols)
    B = len(rows)
    if B == 0 or len(cols) != B:
        return None
    changes = np.nonzero(rows != rows[0])[0]
    Bj = int(changes[0]) if changes.size else B
    if B % Bj:
        return None
    Bi = B // Bj
    urows, ucols = rows[::Bj], cols[:Bj]
    if len(set(urows.tolist())) != Bi or len(set(ucols.tolist())) != Bj:
        return None
    if not (np.array_equal(np.repeat(urows, Bj), rows)
            and np.array_equal(np.tile(ucols, Bi), cols)):
        return None
    return urows, ucols


def _gram_tile_packs(cache: GraphPackCache, tile: int | None, mxu: bool,
                     row_axis: tuple, col_axis: tuple) -> tuple | None:
    """Both axes' packs of a Gram-tile block, on the device, at the
    octile edge the block runs at; None where no edge fits VMEM (the
    block then takes the per-pair route at ``cache.tile``).

    ``tile=None`` tries ``_GRAM_TILE_EDGES`` largest first, an integer
    only itself. An edge is taken where it divides both pads and the
    kernel's envelope (``gram_tile_vmem_bytes``: graph j's whole pack +
    the lane-replicated P panel, growing as n·m·t) fits
    ``_GRAM_TILE_VMEM_BUDGET``. The envelope is sized from the
    host-stacked packs, so only the chosen edge's packs are sent.
    ``row_axis`` / ``col_axis`` are (unique dataset indices, their
    batch)."""
    from repro.kernels.xmv_block_sparse import gram_tile_vmem_bytes
    (urows, g1u), (ucols, g2u) = row_axis, col_axis
    n, m = g1u.adjacency.shape[1], g2u.adjacency.shape[1]
    for t in _GRAM_TILE_EDGES if tile is None else (tile,):
        if n % t or m % t:
            continue
        p1 = cache.stacked_axis(urows, g1u, t)
        p2 = cache.stacked_axis(ucols, g2u, t)
        if gram_tile_vmem_bytes(p1, p2, mxu) <= _GRAM_TILE_VMEM_BUDGET:
            return cache.send(p1), cache.send(p2)
    return None


def gram_pair_step(mesh: Mesh, vertex_kernel: BaseKernel,
                   edge_kernel: BaseKernel, *, method: str = "lowrank",
                   tol: float = 1e-8, max_iter: int = 256,
                   fixed_iters: int | None = None,
                   pcg_variant: str = "classic",
                   sparse_mode: str = "auto",
                   tile: int | None = None,
                   gram_tile: bool = False,
                   segment_size: int | None = None,
                   segment_pad: int = 1,
                   pack_cache_entries: int = 65536,
                   with_grad: bool = False,
                   precond: str = "jacobi",
                   kron_rank: int = 2,
                   pack_dtype=None,
                   guard=True) -> Callable:
    """Build the pair-solve step for a mesh.

    ``guard`` (GuardSpec | bool) enables the per-pair PCG numerical
    guards (core/pcg.py); results then carry the [B] ``status`` bitmask
    the driver's degradation ladder keys on. Every returned step also
    accepts per-call ``fault=``/``spd_margin=`` keywords — the
    deterministic injection seams (distributed/faults.py) — except the
    gradient steps, whose adjoint path has no injection seam (the
    ladder never injects into ``run_with_grad``).

    ``precond="kron"`` solves every block (forward and, under
    ``with_grad``, adjoint) with the Kronecker-factored approximate
    inverse (core/precond.py, DESIGN.md §9); on the sparse path the
    per-graph factors come from the SAME pack cache as the octile
    panels — computed once per (graph, bucket pad), stacked per pair
    block or per Gram-tile axis. ``pack_dtype=jnp.bfloat16`` streams
    the pack value buffers at half the HBM bytes per matvec (f32
    accumulation in-kernel, §9.4).

    ``with_grad=True`` builds a GRADIENT step instead: each pair block
    returns ``(MGKResult, {"vertex.h": [B], "edge.alpha": [B], ...})`` —
    the hyperparameter gradients ∂K/∂θ of every pair, computed by the
    adjoint-PCG custom VJP (core/adjoint.py) in the SAME pass (one
    forward + one adjoint solve per block; DESIGN.md §7). On the sparse
    path the pack cache bakes the ``values_w``/``values_grad`` operand
    buffers once per graph and both solves trust them
    (``trust_pack_weights``), so a graph is decomposed-and-weighted once
    per bucket size for the whole gradient Gram. Gradient steps run
    host-driven (pair-data-parallel over blocks, no "model" sharding).

    ``pcg_variant="pipelined"`` halves the per-iteration all-reduce rounds
    when the product rows are sharded over "model" (DESIGN.md §3/§4);
    ``fixed_iters`` makes every pair of a bucket run the same trip count
    (the paper's load-balancing premise, and a known-size scan for the
    static roofline).

    Forward steps carry ``step.lower(g1, g2[, rows, cols])``: the
    ``jax.stages.Lowered`` program one block's solve compiles (no solve
    runs) — for HLO inspection and compile accounting.

    ``method="pallas_sparse"`` returns a host-driven step: the octile
    row-panel packs are per-graph index structures (not shardable
    tensors), served from a :class:`GraphPackCache` keyed by dataset
    index so each graph is decomposed once per bucket size instead of
    once per pair block; the whole bucket then solves in one row-panel
    kernel launch per CG matvec. ``sparse_mode`` "auto" runs the
    elementwise contraction, the one measured faster on the chip at
    every octile edge and rank a workload runs (DESIGN.md §3.4);
    "mxu" (the low-rank contraction; needs a feature-expandable edge
    kernel) and "elementwise" are honoured as given. Only an MXU step
    builds, stacks and sends the weighted operands. Each block solve
    counts ``xmv.contraction.mxu`` or ``xmv.contraction.elementwise``,
    and ``xmv.octile_edge.<t>`` by the octile edge it ran at
    (:mod:`repro.obs`).
    ``tile`` sets the octile edge (buckets must pad to a multiple);
    ``None`` picks it: Gram-tile blocks as below, every other route at
    t = 8. The step accepts optional ``rows``/``cols`` dataset indices
    (the driver passes them; without them the packs are built uncached).

    ``gram_tile=True`` (sparse only): blocks whose (rows, cols) form a
    rectangle (``data.gram_tile_blocks``) solve in GRAM-TILE execution
    (DESIGN.md §8) — ONE row-panel pack per axis from
    :meth:`GraphPackCache.stacked_axis` (no per-pair restacking) and one
    ``xmv_gram_tile`` launch per matvec, reusing each row graph's
    panels across all its column partners. Under ``tile=None`` such a
    block packs at the largest t in (32, 16, 8) that divides both
    buckets' pads and whose kernel envelope (``gram_tile_vmem_bytes``)
    fits the VMEM budget: t depends only on the pads and the packs'
    k_max (DESIGN.md §3.4). A block that fits at no edge, and every
    non-rectangular block, falls back to the per-pair path at t = 8
    (at ``tile`` where it is given) transparently.

    ``segment_size`` (sparse, forward only): solve with
    convergence-segmented PCG — converged pairs RETIRE between segments
    instead of riding along masked (``mgk_pairs_sparse_segmented``;
    ``segment_pad`` rounds live-batch sizes to bound jit-shape
    diversity). Mutually exclusive with ``fixed_iters``."""
    solve_kw = dict(tol=tol, max_iter=max_iter, fixed_iters=fixed_iters,
                    pcg_variant=pcg_variant)
    precond_kw = dict(precond=precond, kron_rank=kron_rank)
    if method == "pallas_sparse":
        from repro.core.mgk import mgk_pairs_sparse_segmented
        from repro.kernels.ops import row_panel_packs_for_batch

        if segment_size is not None and fixed_iters is not None:
            raise ValueError(
                "segment_size (convergence-segmented PCG) and"
                " fixed_iters (uniform trip count) are mutually"
                " exclusive")
        if segment_size is not None and with_grad:
            raise ValueError(
                "segment_size is forward-only: the adjoint custom_vjp"
                " (run_with_grad) solves with lockstep pcg_solve —"
                " unset segment_size for gradient runs")
        if sparse_mode not in ("auto", "mxu", "elementwise"):
            raise ValueError(f"unknown sparse_mode {sparse_mode!r}")
        if sparse_mode == "mxu" and edge_kernel.feature_rank() is None:
            raise ValueError(
                f"sparse_mode='mxu' needs a feature-expandable edge"
                f" kernel, got {type(edge_kernel).__name__}")
        mode = "mxu" if sparse_mode == "mxu" else "elementwise"
        # only an MXU step builds, stacks and sends the weighted operands
        cache = GraphPackCache(
            tile=_DEFAULT_TILE if tile is None else tile,
            edge_kernel=edge_kernel if mode == "mxu" else None,
            max_entries=pack_cache_entries, with_grad=with_grad,
            pack_dtype=pack_dtype)

        kron = precond == "kron"

        def _note_block(g1, g2, packs):
            """Once per block solve: count the contraction and the octile
            edge it runs, and keep the edge for the scheduler's reads of
            the pack stats (``GramDriver._block_densities``)."""
            obs.count(f"xmv.contraction.{mode}")
            obs.count(f"xmv.octile_edge.{packs.tile}")
            cache.edges[(g1.adjacency.shape[1], g2.adjacency.shape[1])] = \
                packs.tile

        def _block_packs(g1, g2, rows, cols):
            """(packs1, packs2, gram_tile_shape, factors) for one
            block: per-AXIS packs + (Bi, Bj) when the block is a
            rectangle and gram_tile execution is on, else per-pair
            packs + None. ``factors`` are the cached Kronecker
            preconditioner factors — stacked with the SAME granularity
            as the packs (per-axis / per-pair) — or (None, None) under
            Jacobi."""
            axes = _axis_structure(rows, cols) \
                if gram_tile and rows is not None and cols is not None \
                else None
            if axes is not None:
                urows, ucols = axes
                Bi, Bj = len(urows), len(ucols)
                # the flattened pair batch is urows x ucols row-major:
                # unique row graphs sit at strides of Bj, the unique
                # column graphs are the first Bj entries
                g1u = jax.tree.map(lambda x: x[::Bj], g1)
                g2u = jax.tree.map(lambda x: x[:Bj], g2)
                packs = _gram_tile_packs(cache, tile, mode == "mxu",
                                         (urows, g1u), (ucols, g2u))
                if packs is not None:
                    facs = (cache.stacked_factors(urows, g1u),
                            cache.stacked_factors(ucols, g2u)) \
                        if kron else (None, None)
                    return (*packs, (Bi, Bj), facs)
            if rows is None or cols is None:
                p1 = row_panel_packs_for_batch(g1, tile=cache.tile,
                                               edge_kernel=cache.edge_kernel,
                                               with_grad=with_grad)
                p2 = row_panel_packs_for_batch(g2, tile=cache.tile,
                                               edge_kernel=cache.edge_kernel,
                                               with_grad=with_grad)
                facs = (None, None)   # uncached: factors derived in-trace
            else:
                p1 = cache.stacked(rows, g1)
                p2 = cache.stacked(cols, g2)
                facs = (cache.stacked_factors(rows, g1),
                        cache.stacked_factors(cols, g2)) \
                    if kron else (None, None)
            return p1, p2, None, facs

        if with_grad:
            from repro.core.adjoint import flatten_grads, kernel_theta, \
                mgk_value_fn
            theta = kernel_theta(vertex_kernel, edge_kernel)

            def grad_sparse_step(g1, g2, rows=None, cols=None):
                p1, p2, gt, facs = _block_packs(g1, g2, rows, cols)
                _note_block(g1, g2, p1)
                fn = mgk_value_fn(g1, g2, vertex_kernel, edge_kernel,
                                  method="sparse", packs1=p1, packs2=p2,
                                  sparse_mode=mode,
                                  trust_pack_weights=True, gram_tile=gt,
                                  precond_factors=facs,
                                  **solve_kw, **precond_kw)
                vals, grads, sol = fn.value_and_pair_grads(theta,
                                                           with_aux=True)
                res = MGKResult(values=vals, iterations=sol.iterations,
                                converged=sol.converged, nodal=None,
                                status=sol.status)
                return res, flatten_grads(grads)

            grad_sparse_step.pack_cache = cache
            grad_sparse_step.wants_indices = True
            grad_sparse_step.no_pair_pad = gram_tile
            grad_sparse_step.with_grad = True
            return grad_sparse_step

        def _solve_args(g1, g2, rows, cols):
            p1, p2, gt, (f1, f2) = _block_packs(g1, g2, rows, cols)
            return ((g1, g2, p1, p2, vertex_kernel, edge_kernel),
                    dict(sparse_mode=mode, gram_tile=gt,
                         factors1=f1, factors2=f2, guard=guard,
                         **precond_kw))

        def sparse_step(g1: GraphBatch, g2: GraphBatch,
                        rows=None, cols=None, fault=None,
                        spd_margin=None) -> MGKResult:
            args, kw = _solve_args(g1, g2, rows, cols)
            _note_block(*args[:3])          # g1, g2 and the row packs
            if segment_size is not None:
                res = mgk_pairs_sparse_segmented(
                    *args, tol=tol, max_iter=max_iter,
                    segment_size=segment_size, pad_multiple=segment_pad,
                    pcg_variant=pcg_variant, fault=fault,
                    spd_margin=spd_margin, **kw)
            else:
                res = mgk_pairs_sparse(*args, fault=fault,
                                       spd_margin=spd_margin,
                                       **solve_kw, **kw)
            return MGKResult(values=res.values, iterations=res.iterations,
                             converged=res.converged, nodal=None,
                             matvec_pairs=res.matvec_pairs,
                             status=res.status)

        def lower_sparse(g1, g2, rows=None, cols=None):
            if segment_size is not None:
                raise ValueError("segmented PCG is host-driven: there is"
                                 " no single program to lower")
            args, kw = _solve_args(g1, g2, rows, cols)
            return mgk_pairs_sparse.lower(*args, **solve_kw, **kw)

        sparse_step.lower = lower_sparse
        sparse_step.pack_cache = cache
        sparse_step.wants_indices = True
        sparse_step.no_pair_pad = gram_tile
        return sparse_step

    if with_grad:
        from repro.core.adjoint import flatten_grads, kernel_theta, \
            mgk_value_fn
        theta = kernel_theta(vertex_kernel, edge_kernel)

        def grad_step(g1: GraphBatch, g2: GraphBatch):
            fn = mgk_value_fn(g1, g2, vertex_kernel, edge_kernel,
                              method=method, **solve_kw, **precond_kw)
            vals, grads, sol = fn.value_and_pair_grads(theta,
                                                       with_aux=True)
            res = MGKResult(values=vals, iterations=sol.iterations,
                            converged=sol.converged, nodal=None,
                            status=sol.status)
            return res, flatten_grads(grads)

        grad_step.with_grad = True
        return grad_step

    (g1_s, g2_s), out_s = pair_shardings(mesh)

    def step(g1: GraphBatch, g2: GraphBatch) -> MGKResult:
        res = mgk_pairs(g1, g2, vertex_kernel, edge_kernel, method=method,
                        guard=guard, **solve_kw, **precond_kw)
        return MGKResult(values=res.values, iterations=res.iterations,
                         converged=res.converged, nodal=None,
                         matvec_pairs=res.matvec_pairs, status=res.status)

    jstep = jax.jit(step, in_shardings=(g1_s, g2_s), out_shardings=out_s)

    def dense_step(g1: GraphBatch, g2: GraphBatch, fault=None,
                   spd_margin=None) -> MGKResult:
        # clean calls take the jitted sharded step (one trace for the
        # whole build); an injected call routes around it — faults are
        # static jit arguments, so threading them through jstep would
        # retrace per distinct fault AND leak the fault into the cached
        # clean trace's key space
        if fault is None and spd_margin is None:
            return jstep(g1, g2)
        res = mgk_pairs(g1, g2, vertex_kernel, edge_kernel, method=method,
                        guard=guard, fault=fault, spd_margin=spd_margin,
                        **solve_kw, **precond_kw)
        return MGKResult(values=res.values, iterations=res.iterations,
                         converged=res.converged, nodal=None,
                         matvec_pairs=res.matvec_pairs, status=res.status)

    dense_step.lower = jstep.lower
    return dense_step


def _pad_batch(gb: GraphBatch, to: int) -> GraphBatch:
    """Pad the pair axis to a multiple of the data-parallel width with
    self-decoupled dummy pairs (mask 0, degree 1)."""
    B = gb.adjacency.shape[0]
    if B == to:
        return gb
    pad = to - B

    def pad_leaf(x, fill=0.0):
        shape = (pad,) + x.shape[1:]
        return jnp.concatenate([x, jnp.full(shape, fill, x.dtype)])

    return GraphBatch(
        adjacency=pad_leaf(gb.adjacency),
        edge_labels=pad_leaf(gb.edge_labels),
        vertex_labels=pad_leaf(gb.vertex_labels),
        start_prob=pad_leaf(gb.start_prob),
        stop_prob=pad_leaf(gb.stop_prob),
        degrees=pad_leaf(gb.degrees, 1.0),
        node_mask=pad_leaf(gb.node_mask),
        n_nodes=pad_leaf(gb.n_nodes),
    )


def _block_args(ds: BucketedDataset, block: PairBlock, step: Callable,
                pair_width: int) -> tuple:
    """The positional arguments ``step`` takes for one block: both
    (pair-padded) batches, plus the dataset indices for a pack-caching
    sparse step (dummy pairs appended by _pad_batch key as -1 inside
    the cache)."""
    g1 = ds.batch(block.rows, pad_to=block.pad_row)
    g2 = ds.batch(block.cols, pad_to=block.pad_col)
    B = block.n_pairs
    # Gram-tile steps keep the exact Bi x Bj rectangle (host-driven, no
    # pair-axis sharding to pad for — dummy pairs would break it)
    to = B if getattr(step, "no_pair_pad", False) \
        else -(-B // pair_width) * pair_width
    args = (_pad_batch(g1, to), _pad_batch(g2, to))
    if getattr(step, "wants_indices", False):
        args += (block.rows, block.cols)
    return args


def solve_pair_block(ds: BucketedDataset, block: PairBlock, step: Callable,
                     pair_width: int, fault=None,
                     spd_margin=None) -> dict[str, np.ndarray]:
    """Run one PairBlock through the sharded step; returns host arrays.

    ``fault``/``spd_margin`` forward to the step's injection seams
    (only passed when set — gradient steps don't take them). The
    results come back in one device-to-host read, with the solve's
    ``matvec_pairs`` (counted, not returned)."""
    B = block.n_pairs
    kw = {}
    if fault is not None:
        kw["fault"] = fault
    if spd_margin is not None:
        kw["spd_margin"] = spd_margin
    args = _block_args(ds, block, step, pair_width)
    with obs.span("mgk.dispatch"):
        res = step(*args, **kw)
    grads = None
    if getattr(step, "with_grad", False):
        res, grads = res
    with obs.span("mgk.readback"):
        obs.count("host_syncs")
        values, iterations, status, matvec_pairs, grads = jax.device_get(
            (res.values, res.iterations, res.status, res.matvec_pairs,
             grads))
    if matvec_pairs is not None:
        obs.count("matvec_pairs", matvec_pairs)
    out = {
        "rows": np.asarray(block.rows),
        "cols": np.asarray(block.cols),
        "values": values[:B],
        "iterations": iterations[:B],
    }
    if status is not None:
        out["status"] = status[:B]
    if grads is not None:
        # ∂K/∂θ blocks ride along as extra arrays, one per flat key
        out.update({f"grad_{k}": v[:B] for k, v in grads.items()})
    return out


@dataclasses.dataclass
class GramDriver:
    """End-to-end fault-tolerant all-pairs driver.

    Usage:
        driver = GramDriver(ds, mesh, vertex_kernel, edge_kernel, store)
        gram = driver.run()            # resumable; returns [N, N] matrix

    ``gram_tile=True`` (with ``method="pallas_sparse"``) switches block
    generation to rectangular ``tile_shape`` Gram tiles and the solve to
    Gram-tile execution (per-axis packs + ``xmv_gram_tile``, DESIGN.md
    §8); ``segment_size`` additionally retires converged pairs between
    PCG segments (forward ``run()`` only — ``run_with_grad`` raises,
    its adjoint custom_vjp solves lockstep). ``plan()`` feeds MEASURED
    sparsity (pack-cache octile
    stats) and observed per-pair CG iteration counts (finished blocks in
    the store) back into the scheduler's cost model.

    SELF-HEALING (module docstring; DESIGN.md §10.2): with ``guard``
    on (default), each block's per-pair PCG status is health-checked and
    an unhealthy block walks :meth:`_ladder` — ``max_block_retries``
    same-rung retries, then cumulative escalation down to the dense
    reference oracle; pairs broken on the last rung are quarantined
    (dropped from the block, recorded in the manifest ``meta`` and in
    ``self.health``). ``faults`` takes a
    :class:`~repro.distributed.faults.FaultInjector` whose hooks the
    driver calls at the two seams (solve-time, post-save) — None in
    production. After a run, ``self.health`` holds retry/escalation
    counters, the quarantined (i, j) list, a per-block recovery trail,
    and the per-bucket count of pairs that hit max_iter without
    reaching tol (also journaled via ``store.note`` and logged), and
    under ``"counters"`` what the program's counters (:mod:`repro.obs`)
    gained during the run. Each run is a span ``mgk.build``, each block
    a span ``mgk.block`` (attribute ``block``), each ladder attempt
    after a block's first a span ``mgk.retry`` (attribute ``rung``).
    """
    ds: BucketedDataset
    mesh: Mesh
    vertex_kernel: BaseKernel = Constant(1.0)
    edge_kernel: BaseKernel = Constant(1.0)
    store: ChunkStore | None = None
    method: str = "lowrank"
    tol: float = 1e-8
    max_iter: int = 256
    fixed_iters: int | None = None
    pcg_variant: str = "classic"
    sparse_mode: str = "auto"     # pallas_sparse: "auto" | "mxu" | ...
    tile: int | None = None       # octile edge; None picks (§3.4)
    pairs_per_block: int = 64
    gram_tile: bool = False       # Gram-tile execution (sparse only)
    tile_shape: tuple[int, int] = (8, 8)   # unique graphs per tile axis
    segment_size: int | None = None        # segmented PCG (sparse only)
    segment_pad: int = 1
    pack_cache_entries: int = 65536        # GraphPackCache LRU bound
    precond: str = "jacobi"                # "jacobi" | "kron" (§9)
    kron_rank: int = 2                     # Kronecker terms, 1 or 2
    pack_dtype: object = None              # e.g. jnp.bfloat16 (§9.4)
    normalize: bool = True
    guard: object = True                   # GuardSpec | bool (§10.1)
    faults: object = None                  # FaultInjector | None (§10.4)
    max_block_retries: int = 1             # same-rung retries per rung

    def __post_init__(self):
        self._pack_cache = None   # set by _run (the step's cache)
        self._iter_stats: dict[int, float] = {}  # block id -> mean iters
        self._step_cache: dict = {}   # (with_grad, overrides) -> step
        self._block_failures: dict[int, int] = {}
        self.health: dict = self._fresh_health()
        if self.gram_tile and self.method != "pallas_sparse":
            raise ValueError(
                "gram_tile execution needs method='pallas_sparse'")

    @staticmethod
    def _fresh_health() -> dict:
        return {"retries": 0, "escalations": 0, "quarantined_pairs": [],
                "blocks": {}, "nonconverged_by_bucket": {}}

    def blocks(self) -> list[PairBlock]:
        if self.gram_tile:
            from repro.data.loader import gram_tile_blocks
            return list(gram_tile_blocks(self.ds, *self.tile_shape))
        return list(pair_blocks(self.ds, self.pairs_per_block))

    def plan(self, blocks: list[PairBlock] | None = None) -> SchedulePlan:
        blocks = blocks if blocks is not None else self.blocks()
        done = self.store.done_blocks() if self.store else set()
        n_groups = max(
            1, self.mesh.devices.size // self._pair_width())
        return replan(blocks, done, n_groups,
                      densities=self._block_densities(blocks),
                      iters=self._block_iters(blocks, done),
                      precond=self.precond,
                      failures=self._failure_map(blocks))

    def _failure_map(self, blocks) -> dict[int, int] | None:
        """Observed solve-failure counts expanded BUCKET-wise for the
        scheduler: a failing pair usually indicts its bucket's
        conditioning (graph sizes / label distribution), so every block
        of that bucket pair is deprioritized, direct failures keeping
        their own (higher) counts."""
        if not self._block_failures:
            return None
        by_id = {b.block_id: b for b in blocks}
        by_bucket: dict[tuple, int] = {}
        for bid, cnt in self._block_failures.items():
            blk = by_id.get(bid)
            if blk is not None:
                key = (blk.bucket_row, blk.bucket_col)
                by_bucket[key] = max(by_bucket.get(key, 0), cnt)
        out = {}
        for b in blocks:
            cnt = by_bucket.get((b.bucket_row, b.bucket_col), 0)
            cnt = max(cnt, self._block_failures.get(b.block_id, 0))
            if cnt:
                out[b.block_id] = cnt
        return out or None

    def _block_densities(self, blocks) -> dict[int, float] | None:
        """Measured per-block octile occupancy from the pack cache's
        stats (scheduler satellite), at the octile edge the block's
        bucket pair ran at: the product system touches d_row * d_col of
        the tile products, and estimate_cost squares its density knob,
        so the block estimate is sqrt(d_r * d_c)."""
        cache = self._pack_cache
        if cache is None or not cache.stats:
            return None
        out = {}
        for b in blocks:
            t = cache.edges.get((b.pad_row, b.pad_col), cache.tile)
            dr = [cache.density(int(i), b.pad_row, t)
                  for i in set(b.rows.tolist())]
            dc = [cache.density(int(i), b.pad_col, t)
                  for i in set(b.cols.tolist())]
            dr = [d for d in dr if d is not None]
            dc = [d for d in dc if d is not None]
            if dr and dc:
                out[b.block_id] = float(
                    np.sqrt(np.mean(dr) * np.mean(dc)))
        return out or None

    def _block_iters(self, blocks, done) -> dict[int, float] | None:
        """Predicted CG iterations per block from OBSERVED per-pair
        iteration counts of finished blocks (PCGResult.iterations
        persisted in the store), averaged per bucket pair — the paper's
        'iteration count varies with sparsity pattern' feedback loop."""
        if not self.store or not done:
            return None
        by_id = {b.block_id: b for b in blocks}
        per_bucket: dict = {}
        for bid in done:
            blk = by_id.get(bid)
            if blk is None:
                continue
            # memoized per block: a finished block's record is
            # immutable, so each npz is read (and CRC-checked) at most
            # once per driver even across repeated plan()/replan calls
            mean_it = self._iter_stats.get(bid)
            if mean_it is None:
                # planning must survive a corrupt chunk: quarantine it
                # (the run loop recomputes) instead of aborting the plan
                rec = self.store.load_block(bid, on_error="quarantine")
                if rec is None or len(rec["iterations"]) == 0:
                    continue
                mean_it = float(np.mean(rec["iterations"]))
                self._iter_stats[bid] = mean_it
            per_bucket.setdefault(
                (blk.bucket_row, blk.bucket_col), []).append(mean_it)
        if not per_bucket:
            return None
        mean = {k: float(np.mean(v)) for k, v in per_bucket.items()}
        return {b.block_id: mean[(b.bucket_row, b.bucket_col)]
                for b in blocks
                if (b.bucket_row, b.bucket_col) in mean} or None

    def _pair_width(self) -> int:
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        w = 1
        for a, s in sizes.items():
            if a != "model":
                w *= s
        return w

    # -- degradation ladder (DESIGN.md §10.2) -----------------------------
    def _ladder(self, with_grad: bool) -> list[tuple[str, dict | None]]:
        """Ordered (name, CUMULATIVE overrides) rungs; ``None`` overrides
        = the dense numpy reference oracle. Rungs only exist for features
        the driver actually uses (a jacobi/f32/lockstep build starts at
        its own floor). ``run_with_grad`` stops before the oracle — the
        reference path has no hyperparameter gradients, and a gradient
        Gram with silently missing ∂K/∂θ entries would be worse than a
        quarantined pair."""
        rungs: list[tuple[str, dict | None]] = [("base", {})]
        cum: dict = {}
        if self.precond != "jacobi":
            cum = dict(cum, precond="jacobi")
            rungs.append(("jacobi-precond", dict(cum)))
        if self.pack_dtype is not None:
            cum = dict(cum, pack_dtype=None)
            rungs.append(("f32-packs", dict(cum)))
        if self.segment_size is not None and not with_grad:
            cum = dict(cum, segment_size=None)
            rungs.append(("lockstep-pcg", dict(cum)))
        if not with_grad:
            rungs.append(("reference", None))
        return rungs

    def _build_step(self, with_grad: bool, overrides: dict) -> Callable:
        """The pair-solve step for one ladder rung, cached per
        (with_grad, overrides) — rung steps (and their jit traces /
        pack caches) build once per driver, not once per sick block."""
        key = (with_grad, tuple(sorted(overrides.items())))
        step = self._step_cache.get(key)
        if step is None:
            cfg = dict(method=self.method, tol=self.tol,
                       max_iter=self.max_iter,
                       fixed_iters=self.fixed_iters,
                       pcg_variant=self.pcg_variant,
                       sparse_mode=self.sparse_mode, tile=self.tile,
                       gram_tile=self.gram_tile,
                       segment_size=self.segment_size,
                       segment_pad=self.segment_pad,
                       pack_cache_entries=self.pack_cache_entries,
                       with_grad=with_grad, precond=self.precond,
                       kron_rank=self.kron_rank,
                       pack_dtype=self.pack_dtype, guard=self.guard)
            cfg.update(overrides)
            step = gram_pair_step(self.mesh, self.vertex_kernel,
                                  self.edge_kernel, **cfg)
            self._step_cache[key] = step
        return step

    @staticmethod
    def _bad_pairs(out: dict) -> np.ndarray:
        """[B] bool: pairs whose solve is unhealthy — non-finite value,
        or any _UNHEALTHY status bit (guards tripped / restart taken)."""
        bad = ~np.isfinite(np.asarray(out["values"], np.float64))
        status = out.get("status")
        if status is not None:
            bad |= (np.asarray(status) & _UNHEALTHY) != 0
        return bad

    def _reference_block(self, block: PairBlock) -> dict:
        """Final ladder rung: the dense numpy direct solve
        (core/reference.py) pair by pair — no Pallas, no PCG, no
        preconditioner; slow but assumption-free."""
        from repro.core.reference import mgk_direct
        rows = np.asarray(block.rows)
        cols = np.asarray(block.cols)
        vals = np.empty(len(rows), np.float64)
        for k, (r, c) in enumerate(zip(rows, cols)):
            try:
                vals[k] = mgk_direct(self.ds.graphs[int(r)],
                                     self.ds.graphs[int(c)],
                                     self.vertex_kernel, self.edge_kernel)
            except np.linalg.LinAlgError:
                vals[k] = np.nan    # truly singular pair -> quarantine
        return {"rows": rows, "cols": cols, "values": vals,
                "iterations": np.zeros(len(rows), np.int32),
                "status": np.zeros(len(rows), np.int32)}

    def _solve_block_healed(self, block: PairBlock, with_grad: bool,
                            width: int) -> tuple[dict, dict | None]:
        """Solve one block through the degradation ladder.

        Returns ``(out, meta)``: the (possibly pair-filtered) block
        arrays and a JSON-serializable health record for the manifest —
        None when the first attempt came back clean (the ~always case).
        A transient fault is healed by the same-rung retry recomputing
        the block on a clean trajectory, so the saved arrays are
        BITWISE-IDENTICAL to a fault-free run's; only escalation (a
        persistent defect) changes numerics, and only quarantine drops
        pairs — both recorded, never silent."""
        bid = block.block_id
        inj = self.faults if (self.faults is not None
                              and not with_grad) else None
        trail: list[dict] = []
        attempt = 0
        out = None
        for rung_idx, (rung_name, overrides) in enumerate(
                self._ladder(with_grad)):
            if rung_idx > 0:
                self.health["escalations"] += 1
            # the oracle is deterministic — retrying it verbatim is pure
            # waste, so it gets exactly one attempt
            tries = 1 if overrides is None else self.max_block_retries + 1
            for retry in range(tries):
                if retry > 0:
                    self.health["retries"] += 1
                with obs.span("mgk.retry", rung=rung_idx) if attempt \
                        else contextlib.nullcontext():
                    if overrides is None:
                        out = self._reference_block(block)
                    else:
                        step = self._build_step(with_grad, overrides)
                        fault = inj.block_fault(bid, attempt) \
                            if inj else None
                        margin = inj.block_spd_margin(
                            bid, attempt,
                            overrides.get("precond", self.precond)) \
                            if inj else None
                        out = solve_pair_block(self.ds, block, step, width,
                                               fault=fault,
                                               spd_margin=margin)
                attempt += 1
                bad = self._bad_pairs(out)
                if not bad.any():
                    meta = {"recovery": trail} if trail else None
                    return out, meta
                trail.append({"rung": rung_name, "attempt": attempt - 1,
                              "bad_pairs": int(bad.sum())})
                self._block_failures[bid] = \
                    self._block_failures.get(bid, 0) + 1
        # ladder exhausted: quarantine the poison pairs — exclude them
        # from the block (and hence the Gram) and account for every one
        bad = self._bad_pairs(out)
        keep = ~bad
        qpairs = [[int(r), int(c)] for r, c
                  in zip(np.asarray(out["rows"])[bad],
                         np.asarray(out["cols"])[bad])]
        out = {k: np.asarray(v)[keep] for k, v in out.items()}
        self.health["quarantined_pairs"].extend(qpairs)
        logger.warning(
            "block %d: quarantined %d pair(s) after exhausting the "
            "degradation ladder: %s", bid, len(qpairs), qpairs)
        return out, {"recovery": trail, "quarantined_pairs": qpairs}

    def _nonconvergence_summary(self, results: dict[int, dict],
                                by_id: dict) -> None:
        """Tally pairs that ran to max_iter without reaching tol
        (PCG_MAX_ITER without a guard cause — slow, not sick) per bucket
        pair; surface via health, log, and the manifest journal.
        Satellite of DESIGN.md §10: slow convergence must be VISIBLE
        (it skews the cost model and hints at conditioning trouble) but
        is not escalated — the values are finite and sane."""
        per_bucket: dict[str, int] = {}
        for bid, rec in results.items():
            status = rec.get("status")
            if status is None:
                continue
            n_slow = int(((np.asarray(status) & PCG_MAX_ITER) != 0).sum())
            if not n_slow:
                continue
            blk = by_id.get(bid)
            key = f"{blk.bucket_row}x{blk.bucket_col}" if blk is not None \
                else f"block{bid}"
            per_bucket[key] = per_bucket.get(key, 0) + n_slow
        if not per_bucket:
            return
        self.health["nonconverged_by_bucket"] = per_bucket
        logger.warning(
            "%d pair(s) hit max_iter=%d without reaching tol=%g "
            "(per bucket pair: %s) — consider raising max_iter or "
            "loosening tol for these buckets",
            sum(per_bucket.values()), self.max_iter, self.tol,
            per_bucket)
        if self.store:
            self.store.note(kind="nonconvergence", buckets=per_bucket,
                            max_iter=int(self.max_iter),
                            tol=float(self.tol))

    def lower_block(self, block: PairBlock) -> "jax.stages.Lowered":
        """The program the base rung compiles for ``block`` (forward
        solve), lowered ahead of time; nothing is solved. For HLO
        inspection (is the Pallas kernel a ``tpu_custom_call``?) and
        compile accounting."""
        step = self._build_step(False, {})
        return step.lower(*_block_args(self.ds, block, step,
                                       self._pair_width()))

    def run(self, progress: Callable[[int, int], None] | None = None
            ) -> np.ndarray:
        return self._run(progress, with_grad=False)[0]

    def run_with_grad(
        self, progress: Callable[[int, int], None] | None = None
    ) -> tuple[np.ndarray, dict[str, np.ndarray]]:
        """Compute the Gram matrix AND its hyperparameter gradient blocks
        ``{"vertex.h": [N,N], "edge.alpha": [N,N], ...}`` in one pass
        (one forward + one adjoint PCG solve per pair block; the sparse
        pack cache is shared between both solves). With
        ``normalize=True`` the gradients are of the NORMALIZED Gram
        K̂_ij = K_ij / sqrt(K_ii K_jj):

            ∂K̂_ij = ∂K_ij / sqrt(K_ii K_jj)
                    - K̂_ij (∂K_ii / K_ii + ∂K_jj / K_jj) / 2
        """
        return self._run(progress, with_grad=True)

    def _run(self, progress, with_grad: bool):
        self.health = self._fresh_health()
        before = obs.counters()
        try:
            with obs.span("mgk.build"):
                return self._build(progress, with_grad)
        finally:
            self.health["counters"] = obs.delta(before)

    def _build(self, progress, with_grad: bool):
        step = self._build_step(with_grad, {})
        self._pack_cache = getattr(step, "pack_cache", None)
        blocks = self.blocks()
        by_id = {b.block_id: b for b in blocks}
        done = self.store.done_blocks() if self.store else set()
        todo = [b.block_id for b in blocks if b.block_id not in done]
        width = self._pair_width()
        results: dict[int, dict] = {}
        pending = list(todo)
        n_done = 0
        while pending:
            bid = pending.pop(0)
            with obs.span("mgk.block", block=bid):
                out, meta = self._solve_block_healed(by_id[bid], with_grad,
                                                     width)
                if meta:
                    self.health["blocks"][bid] = meta
                if self.store:
                    self.store.save_block(bid, meta=meta, **out)
                    if self.faults is not None:
                        # injection seam: may corrupt the chunk on disk
                        # and/or raise DriverKilled (mid-build crash)
                        self.faults.after_block_saved(self.store, bid)
                else:
                    results[bid] = out
            n_done += 1
            if progress:
                progress(n_done, len(todo))
            if meta and pending and self._block_failures.get(bid):
                # deprioritize blocks sharing a failing bucket pair so
                # healthy work lands first (mirrors plan()'s failures
                # feedback for the in-order walk)
                fmap = self._failure_map(
                    [by_id[b] for b in pending]) or {}
                pending.sort(key=lambda b: fmap.get(b, 0))
        n = len(self.ds)
        if self.store:
            # restore every completed block, quarantining (instead of
            # aborting on) chunks whose CRC no longer matches — then
            # recompute exactly the quarantined/missing ones. The
            # recompute saves WITHOUT the after_block_saved fault seam:
            # a deterministic corruption fault would otherwise re-abuse
            # the same block forever.
            results = {}
            for bid in sorted(self.store.done_blocks()):
                rec = self.store.load_block(bid, on_error="quarantine")
                if rec is not None:
                    results[bid] = dict(rec)
            missing = [b.block_id for b in blocks
                       if b.block_id not in results]
            for bid in missing:
                with obs.span("mgk.block", block=bid):
                    out, meta = self._solve_block_healed(by_id[bid],
                                                         with_grad, width)
                    if meta:
                        self.health["blocks"][bid] = meta
                    self.store.save_block(bid, meta=meta, **out)
                results[bid] = out
        if with_grad:
            # a store populated by a plain run() has value-only blocks;
            # recompute those in memory (save_block is first-writer-wins,
            # so the store keeps its value-only records) instead of
            # silently assembling empty/partial gradients
            want = [f"grad_vertex.{p}" for p in
                    self.vertex_kernel.param_names()] + \
                   [f"grad_edge.{p}" for p in
                    self.edge_kernel.param_names()]
            for bid, out in list(results.items()):
                if any(k not in out for k in want):
                    if bid not in by_id:
                        raise ValueError(
                            f"store block {bid} lacks gradient arrays and"
                            f" is not part of the current block plan"
                            f" (pairs_per_block changed?) — rerun with the"
                            f" original pairs_per_block or a fresh store")
                    with obs.span("mgk.block", block=bid):
                        results[bid], _ = self._solve_block_healed(
                            by_id[bid], with_grad, width)

        self._nonconvergence_summary(results, by_id)

        from .checkpoint import assemble_blocks

        # quarantined pairs leave NaN holes by design: loud (health
        # record, manifest, warning) but not fatal — downstream can mask
        # them via np.isnan. With nothing quarantined, a hole is a BUG
        # and assemble_blocks raises.
        strict = not self.health["quarantined_pairs"]

        def assemble(key):
            return assemble_blocks(results.values(), n, key,
                                   strict=strict)

        with obs.span("mgk.assemble"):
            K = assemble("values")
            grads = None
            if with_grad:
                keys = [k for k in next(iter(results.values()))
                        if k.startswith("grad_")]
                grads = {k[len("grad_"):]: assemble(k) for k in keys}
            if self.normalize:
                d = np.sqrt(np.diag(K))
                Kn = K / d[:, None] / d[None, :]
                if grads is not None:
                    grads = {
                        name: (g / d[:, None] / d[None, :]
                               - 0.5 * Kn
                               * (np.diag(g) / np.diag(K))[:, None]
                               - 0.5 * Kn
                               * (np.diag(g) / np.diag(K))[None, :])
                        for name, g in grads.items()}
                K = Kn
        return K, grads
