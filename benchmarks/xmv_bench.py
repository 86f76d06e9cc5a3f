"""CG hot-path timings on the CPU/interpret harness.

Emits ``BENCH_xmv.json`` with

* per-matvec wall time of the block-sparse bucket XMV — the row-panel
  kernel (one launch, one grid step per output block, in-kernel slot
  reduction over VMEM-staged tile rows) — at several bucket sizes B;
* the same swept over octile edge t in {8, 16, 32};
* fused diagonal epilogue vs the two-step ``diag*p - y`` reference on
  the dense batched path;
* classic vs pipelined PCG on the same product systems: wall time per
  solve, *marginal* wall time per iteration (obtained by differencing
  two ``fixed_iters`` trip counts, which cancels setup/dispatch
  overhead), and the per-pair iteration counts (must agree within ±1).

Numbers here come from the CPU/interpret harness — the absolute times
are not TPU times.

On the pipelined-PCG column: PR 1 recorded pipelined ~27% slower per
solve than classic here despite identical iteration counts. That is an
artifact of the harness, not a solver regression — see the
``pcg["note"]`` field this module emits and DESIGN.md §3.3: each
pipelined iteration runs ~2x the [B, n*m] vector updates (p, s, x, r, u
recurrences + masking vs classic's three AXPYs) plus one extra matvec at
setup (w0 = A u0), costs that XLA op overhead amplifies on a single
interpret-mode CPU device, while the benefit — one all-reduce round per
iteration instead of two — only exists when CG dot products cross
devices. The marginal per-iteration numbers keep the two effects from
being conflated with launch overhead.
"""
from __future__ import annotations

import json

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.base_kernels import KroneckerDelta, SquareExponential
from repro.core.graph import batch_from_graphs
from repro.core.mgk import mgk_pairs_sparse, mgk_pairs_sparse_segmented
from repro.data import make_drugbank_like_dataset
from repro.kernels.ops import row_panel_packs_for_batch
from repro.kernels.xmv_block_sparse import xmv_gram_tile, \
    xmv_row_panel_batched
from repro.kernels.xmv_block_sparse import to_tiles
from repro.kernels.xmv_dense import DENSE_TILE, xmv_dense_batched
from .common import row, time_fn

VK = KroneckerDelta(0.5, n_labels=8)
EK = SquareExponential(1.0, rank=12)

PCG_NOTE = (
    "pipelined > classic per solve on this single-device interpret"
    " harness is expected, not a regression: iteration counts are"
    " identical, but each pipelined iteration performs ~2x the [B, n*m]"
    " vector updates (p/s/x/r/u recurrences + convergence masking vs"
    " classic's three AXPYs) plus one extra matvec at setup (w0 = A u0)."
    " The variant trades those flops for ONE cross-device all-reduce"
    " round per iteration instead of two; with no 'model'-axis sharding"
    " here there is no reduction latency to win back, so only the extra"
    " vector work is visible. us_per_iteration_marginal (fixed_iters"
    " differencing) isolates the loop body from dispatch/setup overhead"
    " so reduction-latency wins on real meshes aren't conflated with"
    " interpret-mode op overhead.")


def _bucket(B: int, pad_to: int, seed: int = 7):
    if pad_to < 6:
        raise ValueError(f"pad_to={pad_to} below the minimum graph size")
    gs = []
    for s in range(seed, seed + 100):
        cand = make_drugbank_like_dataset(2 * B, seed=s)
        gs += [g for g in cand if 6 <= g.n_nodes <= pad_to]
        if len(gs) >= 2 * B:
            break
    else:
        raise RuntimeError(
            f"could not draw {2 * B} graphs with n_nodes in [6, {pad_to}]")
    gs = gs[:2 * B]
    g1 = batch_from_graphs(gs[:B], pad_to=pad_to)
    g2 = batch_from_graphs(gs[B:], pad_to=pad_to)
    return g1, g2


def _row_panel_us(g1, g2, P, iters, tile: int = 8) -> float:
    """Time the whole-bucket row-panel matvec on one bucket."""
    r1 = row_panel_packs_for_batch(g1, tile=tile)
    r2 = row_panel_packs_for_batch(g2, tile=tile)
    Pt = to_tiles(P, tile)     # the row-panel kernels' tile-major order
    return time_fn(lambda P: xmv_row_panel_batched(r1, r2, P, EK),
                   Pt, iters=iters)


def run(out_path: str = "BENCH_xmv.json", sizes=(2, 8, 16),
        pad_to: int = 32, iters: int = 5, tiles=(8, 16, 32),
        tile_pad_to: int = 32, tile_B: int = 4) -> dict:
    rng = np.random.default_rng(0)
    report: dict = {"matvec_block_sparse": [], "matvec_tile_sweep": [],
                    "fused_epilogue": {}, "pcg": {}}

    for B in sizes:
        g1, g2 = _bucket(B, pad_to)
        n = g1.adjacency.shape[1]
        P = jnp.asarray(rng.random((B, n, n)).astype(np.float32))
        us = _row_panel_us(g1, g2, P, iters)
        report["matvec_block_sparse"].append(
            {"B": B, "n": n, "tile": 8, "us_per_matvec_row_panel": us})
        row(f"xmv_sparse_row_panel_B{B}", us, "one-launch")

    # octile-edge sweep
    for t in tiles:
        if tile_pad_to % t:
            continue
        g1, g2 = _bucket(tile_B, tile_pad_to)
        n = g1.adjacency.shape[1]
        P = jnp.asarray(rng.random((tile_B, n, n)).astype(np.float32))
        us = _row_panel_us(g1, g2, P, iters, tile=t)
        report["matvec_tile_sweep"].append(
            {"B": tile_B, "n": n, "tile": t, "us_per_matvec_row_panel": us})
        row(f"xmv_sparse_row_panel_t{t}", us, f"tile={t}")

    # fused diagonal epilogue vs separate XLA op (dense path, largest B)
    B = sizes[-1]
    g1, g2 = _bucket(B, pad_to)
    n = g1.adjacency.shape[1]
    P = to_tiles(jnp.asarray(rng.random((B, n, n)).astype(np.float32)),
                 DENSE_TILE)
    diag = to_tiles(jnp.asarray(rng.random((B, n, n)).astype(np.float32)
                                + 1.0), DENSE_TILE)
    args = (g1.adjacency, g1.edge_labels, g2.adjacency, g2.edge_labels)

    def unfused(P):
        y = xmv_dense_batched(*args, P, EK)
        return diag * P - y

    def fused(P):
        return xmv_dense_batched(*args, P, EK, diag=diag)

    us_unfused = time_fn(unfused, P, iters=iters)
    us_fused = time_fn(fused, P, iters=iters)
    report["fused_epilogue"] = {
        "B": B, "n": n, "us_unfused": us_unfused, "us_fused": us_fused,
        "speedup": us_unfused / max(us_fused, 1e-9),
    }
    row(f"xmv_dense_unfused_B{B}", us_unfused, "separate-diag-op")
    row(f"xmv_dense_fused_B{B}", us_fused, "in-kernel-epilogue")

    # classic vs pipelined PCG on the real sparse product systems (the
    # production row-panel matvec)
    p1 = row_panel_packs_for_batch(g1)
    p2 = row_panel_packs_for_batch(g2)
    pcg: dict = {}
    k_lo, k_hi = 5, 15
    for variant in ("classic", "pipelined"):
        def solve(fixed=None, variant=variant):
            return mgk_pairs_sparse(g1, g2, p1, p2, VK, EK, tol=1e-10,
                                    fixed_iters=fixed,
                                    pcg_variant=variant).values

        us = time_fn(solve, iters=max(2, iters // 2))
        us_lo = time_fn(lambda: solve(k_lo), iters=max(2, iters // 2))
        us_hi = time_fn(lambda: solve(k_hi), iters=max(2, iters // 2))
        us_iter = (us_hi - us_lo) / (k_hi - k_lo)
        res = mgk_pairs_sparse(g1, g2, p1, p2, VK, EK, tol=1e-10,
                               pcg_variant=variant)
        pcg[variant] = {
            "us_per_solve": us,
            "us_per_iteration_marginal": us_iter,
            "iterations": np.asarray(res.iterations).tolist(),
            "converged": bool(np.asarray(res.converged).all()),
        }
        row(f"pcg_{variant}_B{B}", us,
            f"iters={int(np.asarray(res.iterations).max())}"
            f",us/iter={us_iter:.1f}")
    pcg["max_iteration_gap"] = int(np.abs(
        np.asarray(pcg["classic"]["iterations"])
        - np.asarray(pcg["pipelined"]["iterations"])).max())
    pcg["note"] = PCG_NOTE
    report["pcg"] = pcg

    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# wrote {out_path}", flush=True)
    return report


def _gram_batches(Bi: int, Bj: int, pad_to: int, seed: int = 7):
    """(row-axis batch [Bi], col-axis batch [Bj], flattened pair
    batches [Bi*Bj] in row-major pair order)."""
    g1u, g2u = _bucket(max(Bi, Bj), pad_to, seed=seed)
    g1u = jax.tree.map(lambda x: x[:Bi], g1u)
    g2u = jax.tree.map(lambda x: x[:Bj], g2u)
    rep = lambda x: jnp.repeat(x, Bj, axis=0)                   # noqa
    til = lambda x: jnp.tile(x, (Bi,) + (1,) * (x.ndim - 1))    # noqa
    return g1u, g2u, jax.tree.map(rep, g1u), jax.tree.map(til, g2u)


def run_gram(out_path: str = "BENCH_gram.json",
             shapes=((2, 2), (4, 4), (8, 8)), pad_to: int = 32,
             iters: int = 5, segment_size: int = 4) -> dict:
    """Gram-tile hot path vs stacked per-pair row-panel, plus
    convergence-segmented PCG vs masked lockstep.

    Per I x J Gram-tile shape:

    * per-matvec wall time of ``xmv_gram_tile`` (ONE pack per axis,
      (Bi, nt, Bj) grid, in-kernel output-column loop) against
      ``xmv_row_panel_batched`` over per-pair stacked packs. On this
      interpret harness the
      win is the mt-fold grid-step reduction; on hardware it is that
      plus each row graph's panels fetched once per tile row instead of
      once per (pair, tile row).
    * matvecs-per-solve: total pair-matvec evaluations of the segmented
      solve (pairs RETIRE between segments) vs masked lockstep (every
      pair rides to the last pair's convergence), at identical final
      residuals.
    """
    rng = np.random.default_rng(0)
    report: dict = {"gram_tile": [], "segmented_pcg": []}
    for (Bi, Bj) in shapes:
        g1u, g2u, g1f, g2f = _gram_batches(Bi, Bj, pad_to)
        n = g1u.adjacency.shape[1]
        m = g2u.adjacency.shape[1]
        P4 = to_tiles(jnp.asarray(rng.random((Bi, Bj, n, m))
                                  .astype(np.float32)), 8)
        Pf = P4.reshape((Bi * Bj,) + P4.shape[2:])
        # per-axis packs (Bi + Bj) vs per-pair stacked packs (Bi*Bj)
        a1 = row_panel_packs_for_batch(g1u)
        a2 = row_panel_packs_for_batch(g2u)
        p1 = row_panel_packs_for_batch(g1f)
        p2 = row_panel_packs_for_batch(g2f)
        entry = {"Bi": Bi, "Bj": Bj, "n": n, "tile": 8}
        entry["us_per_matvec_per_pair"] = time_fn(
            lambda P: xmv_row_panel_batched(p1, p2, P, EK),
            Pf, iters=iters)
        entry["us_per_matvec_gram_tile"] = time_fn(
            lambda P: xmv_gram_tile(a1, a2, P, EK), P4, iters=iters)
        entry["speedup_gram_tile_vs_per_pair"] = \
            entry["us_per_matvec_per_pair"] / max(
                entry["us_per_matvec_gram_tile"], 1e-9)
        report["gram_tile"].append(entry)
        row(f"xmv_gram_tile_{Bi}x{Bj}", entry["us_per_matvec_gram_tile"],
            f"vs-per-pair={entry['speedup_gram_tile_vs_per_pair']:.2f}x")

        # segmented PCG vs masked lockstep on the same Gram tile (a
        # mixed-convergence bucket: iteration counts vary per pair)
        lock = mgk_pairs_sparse(g1f, g2f, a1, a2, VK, EK, tol=1e-10,
                                gram_tile=(Bi, Bj))
        seg = mgk_pairs_sparse_segmented(
            g1f, g2f, a1, a2, VK, EK, tol=1e-10,
            segment_size=segment_size, gram_tile=(Bi, Bj))
        its = np.asarray(lock.iterations)
        seg_entry = {
            "Bi": Bi, "Bj": Bj, "segment_size": segment_size,
            "matvec_pairs_lockstep": int(lock.matvec_pairs),
            "matvec_pairs_segmented": int(seg.matvec_pairs),
            "iterations_min": int(its.min()),
            "iterations_max": int(its.max()),
            "iterations_match": bool(np.array_equal(
                its, np.asarray(seg.iterations))),
            "values_max_rel_err": float(np.max(np.abs(
                (np.asarray(seg.values) - np.asarray(lock.values))
                / np.maximum(np.abs(np.asarray(lock.values)), 1e-30)))),
            "savings": 1.0 - int(seg.matvec_pairs)
            / max(int(lock.matvec_pairs), 1),
        }
        report["segmented_pcg"].append(seg_entry)
        row(f"pcg_segmented_{Bi}x{Bj}",
            float(seg_entry["matvec_pairs_segmented"]),
            f"lockstep={seg_entry['matvec_pairs_lockstep']}"
            f",savings={seg_entry['savings']:.1%}")
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# wrote {out_path}", flush=True)
    return report


if __name__ == "__main__":
    run()
    run_gram()
