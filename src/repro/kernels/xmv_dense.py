"""Dense on-the-fly Kronecker XMV — the paper's *tiling & blocking*
primitive (Sec. III-C / Appendix F), re-tiled for the TPU memory hierarchy.

A dense graph is the block-sparse case with every octile present, so the
dense kernel IS the row-panel kernel of ``kernels/xmv_block_sparse.py``
run on a dense tiling of (A, E): tile row i holds the nt tiles
``A[i*t:(i+1)*t, k*t:(k+1)*t]`` in column order and every row counts nt
slots. Mapping from the CUDA kernel (DESIGN.md §2):

  CUDA                                  TPU (this kernel)
  ------------------------------------  --------------------------------
  t x t octile staged in shared memory  a tile row of (A, E) and the
                                        partner's tile row staged in VMEM,
                                        double-buffered by the pipeline
  length-r register chunks              lane-flattened [t, t*t] vregs
  warp lanes over product rows          VPU lanes over the partner tile
  out block revisit via grid order      in-kernel slot reduction, one
                                        write per output tile

For every output tile y[i, k] the kernel regenerates the product weights
    w = A[i,j] * A'[k,l] * kappa_e(E[i,j], E'[k,l])
in VMEM/VREGs (never in HBM — the paper's core idea), multiplies by the
P[j,l] tile and accumulates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .xmv_block_sparse import RowPanelPack, to_tiles, xmv_row_panel, \
    xmv_row_panel_batched

__all__ = ["DENSE_TILE", "dense_row_panels", "xmv_dense",
           "xmv_dense_batched"]

# octile edge of the dense tiling (bucket pads are multiples of 8)
DENSE_TILE = 8


def dense_row_panels(A, E, tile: int = DENSE_TILE) -> RowPanelPack:
    """Dense ``[..., n, n]`` adjacency/labels -> a RowPanelPack holding
    every tile: slot k of tile row i is tile column k. Device-side, so
    the dense path needs no host preprocessing."""
    values_adj = to_tiles(A, tile)
    lead, nt = values_adj.shape[:-4], values_adj.shape[-4]
    col = jnp.broadcast_to(jnp.arange(nt, dtype=jnp.int32), lead + (nt, nt))
    count = jnp.full(lead + (nt,), nt, jnp.int32)
    return RowPanelPack(values_adj=values_adj,
                        values_lab=to_tiles(E, tile), values_w=None,
                        col=col, count=count)


@functools.partial(jax.jit,
                   static_argnames=("edge_kernel", "interpret", "acc_dtype"))
def xmv_dense(A, E, Ap, Ep, P, edge_kernel, *, diag=None, interpret=None,
              acc_dtype=jnp.float32, theta=None):
    """Single-pair on-the-fly XMV. A,E: [n,n]; Ap,Ep: [m,m]; P: tile-major
    ``[n/t, m/t, t, t]`` (``to_tiles``), and so is the result.

    With ``diag`` (tile-major like P) the fused epilogue emits
    ``diag * P - y`` in-kernel — the full CG operator application with
    no extra XLA op.

    ``theta`` ([P_theta] f32, ``core.base_kernels.pack_theta`` order)
    overrides the edge kernel's hyperparameters with traced values — the
    differentiable-MGK path (DESIGN.md §7). It rides as a tiny SMEM
    input, so one compiled kernel serves every parameter value."""
    t = P.shape[-1]
    return xmv_row_panel(dense_row_panels(A, E, t),
                         dense_row_panels(Ap, Ep, t), P, edge_kernel,
                         diag=diag, mode="elementwise", interpret=interpret,
                         acc_dtype=acc_dtype, theta=theta)


@functools.partial(jax.jit,
                   static_argnames=("edge_kernel", "interpret", "acc_dtype"))
def xmv_dense_batched(A, E, Ap, Ep, P, edge_kernel, *, diag=None,
                      interpret=None, acc_dtype=jnp.float32, theta=None):
    """Batched over pairs in ONE launch: leading axis B on every operand
    (the TPU analogue of 'many graph pairs per kernel launch', paper
    Sec. V). P and ``diag`` are tile-major ``[B, n/t, m/t, t, t]``;
    ``theta`` (optional, shared across the batch) is the traced
    edge-hyperparameter override."""
    t = P.shape[-1]
    return xmv_row_panel_batched(
        dense_row_panels(A, E, t), dense_row_panels(Ap, Ep, t), P,
        edge_kernel, diag=diag, mode="elementwise", interpret=interpret,
        acc_dtype=acc_dtype, theta=theta)
