"""Pallas TPU kernels for the compute hot-spots (DESIGN.md §2).

  xmv_dense           the paper's tiling & blocking on-the-fly Kronecker XMV
  xmv_block_sparse    inter-tile-sparse octile XMV (scalar prefetch)
  flash_attention     streaming attention for the LM zoo
  ops                 jit'd dispatch wrappers (auto-interpret off-TPU)
  ref                 pure-jnp oracles for all of the above
"""
from . import ops, ref
from .flash_attention import flash_attention
from .xmv_block_sparse import RowPanelPack, from_tiles, \
    pack_graph_row_panels, pack_row_panels, to_tiles, xmv_gram_tile, \
    xmv_row_panel, xmv_row_panel_batched
from .xmv_dense import dense_row_panels, xmv_dense, xmv_dense_batched

__all__ = [
    "ops", "ref", "flash_attention", "RowPanelPack", "pack_row_panels",
    "pack_graph_row_panels", "xmv_row_panel", "xmv_row_panel_batched",
    "xmv_gram_tile", "to_tiles", "from_tiles", "dense_row_panels",
    "xmv_dense", "xmv_dense_batched",
]
