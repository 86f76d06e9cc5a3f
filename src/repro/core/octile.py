"""Octile decomposition — the paper's two-level sparse storage (Sec. IV).

Level 1 (inter-tile): the adjacency/edge-label matrix is cut into t x t
square tiles ("octiles" for t = 8); only non-empty tiles are stored, in a
coordinate (COO-of-tiles) format sorted by (row_tile, col_tile) so that the
TPU block-sparse kernel owns each output block with a contiguous grid range
(the collision-free replacement for the paper's atomics, DESIGN.md §2).

Level 2 (intra-tile): each stored tile carries a multi-word occupancy
bitmap (bit q = i*t + j of word q // 64 is set iff element (i, j) of the
tile is nonzero) plus the packed nonzero values. A t = 8 octile fits one
uint64 word; t = 16 takes 4 words, t = 32 takes 16 — the tile edge is a
parameter throughout the stack (``TILE`` is only the paper's default). On
TPU the compact values are expanded into VMEM before compute, mirroring
the paper's "stored compact, expanded in shared memory".

All functions here are host-side (numpy) preprocessing; they run once per
graph per Gram block, so every per-tile loop is vectorized — at dataset
scale (millions of pair blocks) Python-level tile loops dominate the
preprocessing wall clock otherwise.

Their output feeds the device kernels as dense padded arrays + int32
coordinate lists.
"""
from __future__ import annotations

import dataclasses

import numpy as np

__all__ = [
    "OctileSet",
    "octile_decompose",
    "count_nonempty_tiles",
    "tile_occupancy_histogram",
    "expand_octiles",
    "bitmap_popcounts",
    "bitmap_words",
]

TILE = 8  # the paper's octile edge length (default, not a constraint)


def bitmap_words(tile: int) -> int:
    """Number of 64-bit words an occupancy bitmap of a t x t tile needs."""
    return -(-(tile * tile) // 64)


def bitmap_popcounts(bitmaps: np.ndarray) -> np.ndarray:
    """[K, W] uint64 multi-word bitmaps -> [K] per-tile popcounts.

    Vectorized via a uint8 view + ``np.unpackbits`` (endianness is
    irrelevant to a popcount). A 1-D [K] input (single-word bitmaps) is
    treated as [K, 1].
    """
    bitmaps = np.asarray(bitmaps, np.uint64)
    if bitmaps.ndim == 1:
        bitmaps = bitmaps[:, None]
    if bitmaps.shape[0] == 0:
        return np.zeros((0,), np.int64)
    bits = np.unpackbits(bitmaps.view(np.uint8), axis=1)
    return bits.sum(axis=1).astype(np.int64)


def _pack_bitmaps(nz: np.ndarray, tile: int) -> np.ndarray:
    """[K, t, t] bool occupancy -> [K, W] uint64 multi-word bitmaps."""
    K = nz.shape[0]
    W = bitmap_words(tile)
    flat = nz.reshape(K, tile * tile).astype(np.uint64)
    padded = np.zeros((K, W * 64), np.uint64)
    padded[:, :tile * tile] = flat
    weights = np.uint64(1) << np.arange(64, dtype=np.uint64)
    return (padded.reshape(K, W, 64) * weights).sum(axis=2, dtype=np.uint64)


@dataclasses.dataclass(frozen=True)
class OctileSet:
    """COO-of-octiles representation of one square matrix.

    Attributes:
      tile: tile edge length t.
      n_tiles_side: number of tile rows (= cols) of the padded matrix.
      coords: [K, 2] int32 (tile_row, tile_col) of non-empty tiles, sorted
        row-major.
      bitmaps: [K, W] uint64 occupancy bitmap words per tile
        (W = ceil(t^2 / 64); one word for the paper's t = 8).
      values_adj: [K, t, t] float32 dense tile values of the adjacency.
      values_lab: [K, t, t] float32 dense tile values of the edge labels.
      nnz: total nonzero element count.
    """

    tile: int
    n_tiles_side: int
    coords: np.ndarray
    bitmaps: np.ndarray
    values_adj: np.ndarray
    values_lab: np.ndarray
    nnz: int

    @property
    def n_nonempty(self) -> int:
        return int(self.coords.shape[0])

    @property
    def density(self) -> float:
        """Mean within-tile occupancy of the non-empty tiles."""
        if self.n_nonempty == 0:
            return 0.0
        pop = bitmap_popcounts(self.bitmaps)
        return float(pop.mean()) / (self.tile * self.tile)

    def padded(self, max_tiles: int) -> "OctileSet":
        """Pad the COO lists to a fixed length for jit-stable shapes."""
        K = self.n_nonempty
        if max_tiles < K:
            raise ValueError(f"max_tiles={max_tiles} < {K}")
        pad = max_tiles - K
        W = self.bitmaps.shape[1] if self.bitmaps.ndim == 2 \
            else bitmap_words(self.tile)
        return OctileSet(
            tile=self.tile,
            n_tiles_side=self.n_tiles_side,
            coords=np.concatenate(
                [self.coords, np.full((pad, 2), -1, np.int32)]),
            bitmaps=np.concatenate(
                [self.bitmaps.reshape(K, W),
                 np.zeros((pad, W), np.uint64)]),
            values_adj=np.concatenate(
                [self.values_adj,
                 np.zeros((pad, self.tile, self.tile), np.float32)]),
            values_lab=np.concatenate(
                [self.values_lab,
                 np.zeros((pad, self.tile, self.tile), np.float32)]),
            nnz=self.nnz,
        )


def _pad_to_tiles(mat: np.ndarray, tile: int) -> np.ndarray:
    n = mat.shape[0]
    n_pad = -(-n // tile) * tile
    if n_pad == n:
        return mat
    out = np.zeros((n_pad, n_pad), mat.dtype)
    out[:n, :n] = mat
    return out


def octile_decompose(adjacency: np.ndarray,
                     edge_labels: np.ndarray | None = None,
                     tile: int = TILE) -> OctileSet:
    """Decompose a square matrix into its non-empty t x t tiles."""
    adjacency = _pad_to_tiles(np.asarray(adjacency, np.float32), tile)
    if edge_labels is None:
        edge_labels = np.zeros_like(adjacency)
    edge_labels = _pad_to_tiles(np.asarray(edge_labels, np.float32), tile)
    nt = adjacency.shape[0] // tile
    # [nt, nt, t, t] view
    a4 = adjacency.reshape(nt, tile, nt, tile).transpose(0, 2, 1, 3)
    e4 = edge_labels.reshape(nt, tile, nt, tile).transpose(0, 2, 1, 3)
    occupied = (a4 != 0).any(axis=(2, 3))
    rows, cols = np.nonzero(occupied)
    order = np.lexsort((cols, rows))  # row-major: output-block contiguous
    rows, cols = rows[order], cols[order]
    vals_a = a4[rows, cols]
    vals_e = e4[rows, cols]
    nz = vals_a != 0
    return OctileSet(
        tile=tile,
        n_tiles_side=nt,
        coords=np.stack([rows, cols], axis=1).astype(np.int32),
        bitmaps=_pack_bitmaps(nz, tile),
        values_adj=vals_a.astype(np.float32),
        values_lab=vals_e.astype(np.float32),
        nnz=int(nz.sum()),
    )


def count_nonempty_tiles(adjacency: np.ndarray, tile: int = TILE) -> int:
    """Number of non-empty t x t tiles (the PBR objective, paper Eq. 3)."""
    adjacency = _pad_to_tiles(np.asarray(adjacency), tile)
    nt = adjacency.shape[0] // tile
    a4 = adjacency.reshape(nt, tile, nt, tile).transpose(0, 2, 1, 3)
    return int((a4 != 0).any(axis=(2, 3)).sum())


def tile_occupancy_histogram(adjacency: np.ndarray,
                             tile: int = TILE) -> np.ndarray:
    """Histogram over nonzeros-per-non-empty-tile (paper Fig. 7/8 input)."""
    adjacency = _pad_to_tiles(np.asarray(adjacency), tile)
    nt = adjacency.shape[0] // tile
    a4 = adjacency.reshape(nt, tile, nt, tile).transpose(0, 2, 1, 3)
    counts = (a4 != 0).sum(axis=(2, 3)).ravel()
    counts = counts[counts > 0]
    return np.bincount(counts, minlength=tile * tile + 1)


def expand_octiles(oset: OctileSet) -> tuple[np.ndarray, np.ndarray]:
    """Reconstruct the dense padded (adjacency, labels) from an OctileSet.

    Vectorized scatter into the [nt, nt, t, t] view (coords are unique, so
    fancy-index assignment is exact — no per-tile Python loop).
    """
    t, nt = oset.tile, oset.n_tiles_side
    a4 = np.zeros((nt, nt, t, t), np.float32)
    e4 = np.zeros((nt, nt, t, t), np.float32)
    real = oset.coords[:, 0] >= 0       # skip padded() slots
    rows, cols = oset.coords[real, 0], oset.coords[real, 1]
    a4[rows, cols] = oset.values_adj[real]
    e4[rows, cols] = oset.values_lab[real]
    a = a4.transpose(0, 2, 1, 3).reshape(nt * t, nt * t)
    e = e4.transpose(0, 2, 1, 3).reshape(nt * t, nt * t)
    return a, e
