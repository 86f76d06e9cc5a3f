"""Pallas TPU kernels for the compute hot-spots (DESIGN.md §2).

  xmv_dense           the paper's tiling & blocking on-the-fly Kronecker XMV
  xmv_block_sparse    inter-tile-sparse octile XMV (scalar prefetch)
  flash_attention     streaming attention for the LM zoo
  ops                 jit'd dispatch wrappers (auto-interpret off-TPU)
  ref                 pure-jnp oracles for all of the above
"""
from . import ops, ref
from .flash_attention import flash_attention
from .xmv_block_sparse import RowPanelPack, TilePack, pack_graph, \
    pack_graph_row_panels, pack_octiles, pack_row_panels, \
    from_tiles, to_tiles, xmv_block_sparse, xmv_gram_tile, \
    xmv_row_panel, xmv_row_panel_batched
from .xmv_dense import dense_row_panels, xmv_dense, xmv_dense_batched

__all__ = [
    "ops", "ref", "flash_attention", "TilePack", "RowPanelPack",
    "pack_graph", "pack_octiles", "pack_row_panels",
    "pack_graph_row_panels", "xmv_block_sparse", "xmv_row_panel",
    "xmv_row_panel_batched", "xmv_gram_tile", "to_tiles", "from_tiles",
    "dense_row_panels", "xmv_dense", "xmv_dense_batched",
]
