"""Core marginalized-graph-kernel library (the paper's contribution).

Public surface:
  Graph / GraphBatch        graph containers (host / device)
  base kernels              Constant, KroneckerDelta, SquareExponential, ...
  octile_decompose          two-level sparse tile storage
  rcm_order / pbr_order / morton_order / best_order
  pcg_solve                 batched masked preconditioned CG
  mgk_pairs / mgk_single    the marginalized graph kernel
  mgk_*_value_and_grad      adjoint-solve hyperparameter gradients
"""
from .adjoint import (flatten_grads, kernel_theta,
                      mgk_adaptive_value_and_grad,
                      mgk_pairs_sparse_value_and_grad,
                      mgk_pairs_value_and_grad, mgk_value_fn)
from .base_kernels import (BaseKernel, CompactPolynomial, Constant,
                           KroneckerDelta, ParamDerivative,
                           SquareExponential, pack_theta, unpack_theta)
from .graph import Graph, GraphBatch, batch_from_graphs, pad_graphs
from .mgk import MGKResult, ProductSystem, adaptive_route, \
    build_product_system, mgk_adaptive, mgk_pairs, mgk_pairs_sparse, \
    mgk_pairs_sparse_segmented, mgk_single
from .octile import (OctileSet, count_nonempty_tiles, expand_octiles,
                     octile_decompose, tile_occupancy_histogram)
from .pcg import PCGResult, adjoint_solve, pcg_solve, \
    pcg_solve_segmented
from .precond import (KronFactors, kron_apply, kron_apply_gram,
                      kron_factor_arrays, kron_factors, kron_scalars,
                      stack_kron_factors, take_kron_factors)
from .reorder import best_order, morton_order, pbr_order, rcm_order

__all__ = [
    "BaseKernel", "CompactPolynomial", "Constant", "KroneckerDelta",
    "SquareExponential", "ParamDerivative", "pack_theta", "unpack_theta",
    "Graph", "GraphBatch", "batch_from_graphs",
    "pad_graphs", "MGKResult", "ProductSystem", "build_product_system",
    "mgk_pairs", "mgk_single", "mgk_pairs_sparse",
    "mgk_pairs_sparse_segmented", "mgk_adaptive",
    "adaptive_route", "OctileSet", "count_nonempty_tiles",
    "expand_octiles", "octile_decompose", "tile_occupancy_histogram",
    "PCGResult", "pcg_solve", "pcg_solve_segmented", "adjoint_solve",
    "KronFactors", "kron_factors", "kron_factor_arrays", "kron_scalars",
    "kron_apply", "kron_apply_gram", "take_kron_factors",
    "stack_kron_factors",
    "best_order", "morton_order", "pbr_order", "rcm_order",
    "kernel_theta", "mgk_value_fn", "mgk_pairs_value_and_grad",
    "mgk_pairs_sparse_value_and_grad", "mgk_adaptive_value_and_grad",
    "flatten_grads",
]
