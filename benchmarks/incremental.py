"""Paper Fig. 9 analog: the optimization ladder. Each rung keeps
everything from the rung below and adds one technique; we report
time-to-solution of a fixed batch of kernel evaluations (CPU wall clock,
XLA-jitted -> relative speedups are the signal):

  dense        naive full-product XMV inside CG
  sparse       block-sparse octile XMV (natural order)
  +reorder     PBR reordering before packing
  +lowrank     beyond-paper MXU sandwich XMV (rank-12 SE features)
"""
from __future__ import annotations

import numpy as np

from repro.core import KroneckerDelta, SquareExponential, \
    batch_from_graphs, mgk_pairs, pbr_order
from repro.core.mgk import mgk_pairs_sparse
from repro.data import make_drugbank_like_dataset, make_synthetic_dataset
from repro.kernels.ops import row_panel_packs_for_batch
from .common import row, time_fn

VK = KroneckerDelta(0.5, n_labels=8)
EK = SquareExponential(1.0, rank=12)


def _pairs(dataset: str, n_pairs: int = 4):
    if dataset == "nws":
        gs = make_synthetic_dataset("nws", n_graphs=2 * n_pairs,
                                    n_nodes=48, seed=0)
    elif dataset == "ba":
        gs = make_synthetic_dataset("ba", n_graphs=2 * n_pairs,
                                    n_nodes=48, seed=0)
    else:
        gs = [g for g in make_drugbank_like_dataset(40, seed=0)
              if 24 <= g.n_nodes <= 64][:2 * n_pairs]
    a = gs[:n_pairs]
    b = gs[n_pairs:2 * n_pairs]
    return a, b


def run(datasets=("nws", "ba", "drugbank_like")) -> list[str]:
    out = []
    for ds in datasets:
        ga, gb = _pairs(ds)
        pad = max(max(g.n_nodes for g in ga), max(g.n_nodes for g in gb))
        pad = -(-pad // 8) * 8
        A = batch_from_graphs(ga, pad_to=pad)
        B = batch_from_graphs(gb, pad_to=pad)

        us = time_fn(lambda a, b: mgk_pairs(a, b, VK, EK, method="full",
                                            tol=1e-8).values, A, B, iters=3)
        base = us
        out.append(row(f"ladder_{ds}_dense", us, "speedup=1.00x"))

        packs_a = row_panel_packs_for_batch(A)
        packs_b = row_panel_packs_for_batch(B)
        us = time_fn(lambda a, b, pa, pb: mgk_pairs_sparse(
            a, b, pa, pb, VK, EK, tol=1e-8).values,
            A, B, packs_a, packs_b, iters=3)
        out.append(row(f"ladder_{ds}_sparse", us,
                       f"speedup={base / us:.2f}x"))

        ga_r = [g.permuted(pbr_order(g.adjacency)) for g in ga]
        gb_r = [g.permuted(pbr_order(g.adjacency)) for g in gb]
        Ar = batch_from_graphs(ga_r, pad_to=pad)
        Br = batch_from_graphs(gb_r, pad_to=pad)
        pa_r = row_panel_packs_for_batch(Ar)
        pb_r = row_panel_packs_for_batch(Br)
        us = time_fn(lambda a, b, pa, pb: mgk_pairs_sparse(
            a, b, pa, pb, VK, EK, tol=1e-8).values,
            Ar, Br, pa_r, pb_r, iters=3)
        out.append(row(f"ladder_{ds}_sparse_reorder", us,
                       f"speedup={base / us:.2f}x"))

        us = time_fn(lambda a, b: mgk_pairs(a, b, VK, EK, method="lowrank",
                                            tol=1e-8).values, A, B, iters=3)
        out.append(row(f"ladder_{ds}_lowrank_mxu", us,
                       f"speedup={base / us:.2f}x"))
    return out


if __name__ == "__main__":
    run()
