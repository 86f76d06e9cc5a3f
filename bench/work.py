"""Work model and peaks: the least work a pair's solve needs, counted
from the graphs and not from any kernel's tiles, and the chip's peaks
that a share of a roofline is taken against.

Per pair-matvec of graphs i (n nodes) and j (m nodes):

* FLOPs: nnz(A_i) * nnz(A_j) product edges, each a multiply-add (2) plus
  one evaluation of the edge kernel (``FLOPS`` of its base-kernel file);
* bytes: the P and y vectors of n * m float32 values.

A solve takes (iterations + 1) pair-matvecs: one for the initial
residual, one per PCG iteration.
"""
from __future__ import annotations

import numpy as np

# Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.
# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s (bf16),
# 16 GB of HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
}

BYTES_PER_VALUE = 4


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add"
                       f" them to bench/work.py with their source") from None


def pair_matvec(nnz_i, nnz_j, n_i, n_j, edge_flops: int):
    """(FLOPs, bytes) of one pair-matvec (scalars or arrays)."""
    return (nnz_i * nnz_j * (2 + edge_flops),
            2.0 * n_i * n_j * BYTES_PER_VALUE)


def window_work(rows, cols, iterations, nnz, nodes,
                edge_flops: int) -> tuple[float, float]:
    """Total (FLOPs, bytes) of the solves of the pairs (rows[k],
    cols[k]) that took iterations[k]; ``nnz``/``nodes`` per graph."""
    nnz = np.asarray(nnz, np.float64)
    nodes = np.asarray(nodes, np.float64)
    f, b = pair_matvec(nnz[rows], nnz[cols], nodes[rows], nodes[cols],
                       edge_flops)
    mv = np.asarray(iterations, np.float64) + 1
    return float(np.sum(mv * f)), float(np.sum(mv * b))


def roofline_seconds(flops: float, bytes_: float, pk: dict) -> float:
    """The least time the chip could take: the larger of the compute
    bound and the memory bound."""
    return max(flops / pk["flops_per_s"], bytes_ / pk["bytes_per_s"])
