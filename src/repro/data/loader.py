"""Padding / bucketing loader.

Variable-size graphs must become fixed jit shapes. Strategy (DESIGN.md §4):

1. Bucket graphs by padded size (multiples of the octile edge, capped
   buckets chosen from the dataset's size histogram).
2. Within a bucket, any subset batches into one GraphBatch.
3. All-pairs work is expressed as *pair blocks* — (bucket_i, bucket_j)
   chunks of bounded element count — which are the scheduling/checkpointing
   unit of the distributed Gram driver.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, Sequence

import numpy as np

from repro import obs
from repro.core.graph import Graph, GraphBatch, batch_from_graphs

__all__ = ["BucketedDataset", "bucket_graphs", "pair_blocks",
           "gram_tile_blocks", "PairBlock"]


def _bucket_sizes(sizes: np.ndarray, multiple_of: int,
                  max_buckets: int) -> list[int]:
    """Choose bucket boundaries from the size histogram: quantile-spaced,
    rounded up to the tile multiple (keeps padding waste bounded while
    keeping the number of distinct jit shapes small)."""
    padded = (-(-sizes // multiple_of) * multiple_of).astype(int)
    uniq = np.unique(padded)
    if len(uniq) <= max_buckets:
        return [int(u) for u in uniq]
    qs = np.linspace(0, 1, max_buckets)
    bounds = sorted({int(-(-np.quantile(padded, q) // multiple_of)
                         * multiple_of) for q in qs})
    if bounds[-1] < padded.max():
        bounds.append(int(padded.max()))
    return bounds


@dataclasses.dataclass(frozen=True)
class Bucket:
    pad_to: int
    indices: np.ndarray  # dataset indices of member graphs


@dataclasses.dataclass
class BucketedDataset:
    graphs: list[Graph]
    buckets: list[Bucket]
    multiple_of: int

    def __len__(self) -> int:
        return len(self.graphs)

    def bucket_of(self, idx: int) -> int:
        for bi, b in enumerate(self.buckets):
            if idx in b.indices:
                return bi
        raise KeyError(idx)

    def batch(self, indices: Sequence[int], pad_to: int) -> GraphBatch:
        with obs.span("mgk.batch"):
            return batch_from_graphs([self.graphs[i] for i in indices],
                                     pad_to=pad_to,
                                     multiple_of=self.multiple_of)


def bucket_graphs(graphs: Sequence[Graph], multiple_of: int = 8,
                  max_buckets: int = 8) -> BucketedDataset:
    sizes = np.array([g.n_nodes for g in graphs])
    bounds = _bucket_sizes(sizes, multiple_of, max_buckets)
    assigned = [[] for _ in bounds]
    for i, s in enumerate(sizes):
        for bi, bound in enumerate(bounds):
            if s <= bound:
                assigned[bi].append(i)
                break
    buckets = [Bucket(pad_to=bound, indices=np.array(ix, dtype=np.int64))
               for bound, ix in zip(bounds, assigned) if len(ix)]
    return BucketedDataset(graphs=list(graphs), buckets=buckets,
                           multiple_of=multiple_of)


@dataclasses.dataclass(frozen=True)
class PairBlock:
    """A fixed-shape chunk of all-pairs work: the scheduling unit.

    rows/cols are dataset indices; the block computes every (row, col)
    combination as a flat batch of ``len(rows)`` pairs (rows and cols are
    pre-flattened — rows[k] pairs with cols[k]).
    """
    block_id: int
    bucket_row: int
    bucket_col: int
    rows: np.ndarray
    cols: np.ndarray
    pad_row: int
    pad_col: int

    @property
    def n_pairs(self) -> int:
        return len(self.rows)

    def cost(self) -> float:
        """Cost model for load balancing: Σ (n_i * n_j)^2 — the XMV work of
        one CG iteration (paper Sec. V-B's 'variation of graph size')."""
        return float(self.n_pairs) * (self.pad_row * self.pad_col) ** 2


def gram_tile_blocks(ds: BucketedDataset, tile_rows: int = 8,
                     tile_cols: int = 8,
                     upper_triangular: bool = True) -> Iterator[PairBlock]:
    """All-pairs work as RECTANGULAR Gram tiles (DESIGN.md §8).

    Unlike :func:`pair_blocks` — which chunks the raveled pair list, so
    a block's rows/cols are an arbitrary span of the product — every
    block here is the row-major flattening of ``unique_rows x
    unique_cols`` with at most ``tile_rows`` x ``tile_cols`` unique
    graphs per axis. That rectangle structure is what Gram-tile
    execution exploits: ONE row-panel pack per axis (Bi + Bj packs, not
    Bi*Bj), each row graph's panels reused across all its column
    partners in one ``xmv_gram_tile`` launch.

    On a diagonal bucket pair with ``upper_triangular``, tiles lying
    entirely below the diagonal are skipped; tiles straddling it keep
    their full rectangle (a few redundant mirror pairs — the classic
    tile-vs-triangle trade; the symmetric Gram assembly of
    ``distributed/checkpoint.py`` absorbs them).
    """
    bid = 0
    nb = len(ds.buckets)
    for bi in range(nb):
        for bj in range(bi, nb) if upper_triangular else range(nb):
            r_idx = ds.buckets[bi].indices
            c_idx = ds.buckets[bj].indices
            for r0 in range(0, len(r_idx), tile_rows):
                for c0 in range(0, len(c_idx), tile_cols):
                    if upper_triangular and bi == bj \
                            and c0 + tile_cols <= r0:
                        continue      # tile entirely below the diagonal
                    rch = r_idx[r0:r0 + tile_rows]
                    cch = c_idx[c0:c0 + tile_cols]
                    rr, cc = np.meshgrid(rch, cch, indexing="ij")
                    yield PairBlock(
                        block_id=bid,
                        bucket_row=bi, bucket_col=bj,
                        rows=rr.ravel(), cols=cc.ravel(),
                        pad_row=ds.buckets[bi].pad_to,
                        pad_col=ds.buckets[bj].pad_to)
                    bid += 1


def pair_blocks(ds: BucketedDataset, pairs_per_block: int = 64,
                upper_triangular: bool = True) -> Iterator[PairBlock]:
    """Enumerate all-pairs work as fixed-shape blocks."""
    bid = 0
    nb = len(ds.buckets)
    for bi in range(nb):
        for bj in range(bi, nb) if upper_triangular else range(nb):
            rows_idx = ds.buckets[bi].indices
            cols_idx = ds.buckets[bj].indices
            rr, cc = np.meshgrid(rows_idx, cols_idx, indexing="ij")
            rr, cc = rr.ravel(), cc.ravel()
            if upper_triangular and bi == bj:
                keep = rr <= cc
                rr, cc = rr[keep], cc[keep]
            for s in range(0, len(rr), pairs_per_block):
                yield PairBlock(
                    block_id=bid,
                    bucket_row=bi, bucket_col=bj,
                    rows=rr[s:s + pairs_per_block],
                    cols=cc[s:s + pairs_per_block],
                    pad_row=ds.buckets[bi].pad_to,
                    pad_col=ds.buckets[bj].pad_to)
                bid += 1
