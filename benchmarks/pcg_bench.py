"""PR 5 perf tracking: PCG iteration counts at the source.

Emits ``BENCH_pcg.json`` with, per octile-density bucket (sparse /
medium / dense synthetic fixtures):

* iterations-to-tol (total, mean, max over the bucket) and pair-matvec
  evaluations for ``precond="jacobi"`` vs ``precond="kron"`` — the
  Kronecker-factored approximate inverse of ``core/precond.py``
  (DESIGN.md §9) attacks the iteration COUNT where PRs 1-4 attacked
  per-iteration cost. CI asserts kron reaches tol=1e-6 in ≥30% fewer
  iterations on the dense bucket, with identical solutions;
* end-to-end bucket solve wall-clock (product-system build + PCG to
  tol on the production row-panel MXU matvec) for both preconditioners
  — kron pays two small [n,n] @ X @ [m,m] matmuls per iteration to
  save whole matvecs, so wall-clock must be no worse anywhere and
  strictly better where matvecs dominate;
* bf16 pack streaming (§9.4): HBM bytes per matvec streamed by the
  pack value buffers at f32 vs ``pack_dtype=jnp.bfloat16`` (exactly
  2x) and the measured matvec parity error.

Numbers come from the CPU/interpret harness: absolute times are not
TPU times, but iteration counts are solver-exact and the bytes model
is arithmetic over buffer sizes.
"""
from __future__ import annotations

import json

import numpy as np

import jax.numpy as jnp

from repro.core.base_kernels import Constant, SquareExponential
from repro.core.graph import Graph, batch_from_graphs
from repro.core.mgk import mgk_pairs_sparse
from repro.kernels.ops import row_panel_packs_for_batch
from repro.kernels.xmv_block_sparse import to_tiles, xmv_row_panel_batched
from .common import row, time_fn

VK = Constant(1.0)
EK = SquareExponential(1.0, rank=12)

# (name, kind) buckets spanning the adaptive dispatch table's octile
# density range: molecule-like sparse graphs (band + ring structure,
# low octile occupancy) through erdos-renyi fixtures whose occupancy
# saturates — "dense" is the CI-asserted fixture
BUCKETS = (("sparse", "drugbank"), ("medium", "er:0.15"),
           ("dense", "er:0.40"))


def _bucket(B: int, n: int, kind: str, seed: int, q: float = 0.05):
    """Synthetic fixture bucket with the paper's small stopping
    probability (the near-critical regime where iteration counts hurt
    most). ``kind``: "drugbank" (molecule-like sparse) or "er:<p>"
    (erdos-renyi at edge probability p)."""
    import dataclasses
    rng = np.random.default_rng(seed)
    if kind == "drugbank":
        from repro.data import make_drugbank_like_dataset
        gs = []
        for s in range(seed, seed + 100):
            cand = make_drugbank_like_dataset(2 * B, seed=s)
            gs += [g for g in cand if 6 <= g.n_nodes <= n]
            if len(gs) >= 2 * B:
                break
        # pin the requested stopping probability (the generator has its
        # own default) so every bucket probes the same conditioning
        gs = [dataclasses.replace(
            g, stop_prob=np.full(g.n_nodes, q, np.float32))
            for g in gs[:2 * B]]
    else:
        p = float(kind.split(":")[1])
        gs = []
        for _ in range(2 * B):
            a = (rng.random((n, n)) < p).astype(np.float32)
            a = np.triu(a, 1)
            a = a + a.T
            e = rng.random((n, n)).astype(np.float32)
            e = (e + e.T) / 2 * (a != 0)
            v = rng.integers(0, 4, n).astype(np.float32)
            gs.append(Graph.create(a, e, v, stop_prob=q))
    pad = n + (-n) % 8
    return (batch_from_graphs(gs[:B], pad_to=pad),
            batch_from_graphs(gs[B:], pad_to=pad))


def _pack_bytes(pack) -> int:
    """HBM bytes of the value buffers a matvec streams (indices/counts
    excluded — they are SMEM scalar-prefetch traffic)."""
    total = 0
    for field in ("values_adj", "values_lab"):
        total += getattr(pack, field).nbytes
    return total


def run(out_path: str = "BENCH_pcg.json", B: int = 4, n: int = 32,
        iters: int = 3, tol: float = 1e-6, seed: int = 11) -> dict:
    report: dict = {"tol": tol, "pcg": [], "bf16": {}}

    for name, kind in BUCKETS:
        g1, g2 = _bucket(B, n, kind, seed)
        p1 = row_panel_packs_for_batch(g1)
        p2 = row_panel_packs_for_batch(g2)

        def solve(precond):
            return mgk_pairs_sparse(g1, g2, p1, p2, VK, EK, tol=tol,
                                    precond=precond)

        rj, rk = solve("jacobi"), solve("kron")
        ij = np.asarray(rj.iterations)
        ik = np.asarray(rk.iterations)
        assert bool(np.asarray(rj.converged).all())
        assert bool(np.asarray(rk.converged).all())
        vals_err = float(np.max(np.abs(
            (np.asarray(rk.values) - np.asarray(rj.values))
            / np.maximum(np.abs(np.asarray(rj.values)), 1e-30))))
        # end-to-end bucket solve wall clock, both arms (values output
        # forces the whole pipeline)
        us_j = time_fn(lambda: solve("jacobi").values.block_until_ready(),
                       iters=iters)
        us_k = time_fn(lambda: solve("kron").values.block_until_ready(),
                       iters=iters)
        entry = {
            "bucket": name, "kind": kind, "B": B, "n": n,
            "octile_density": None,   # filled below from pack stats
            "iters_jacobi_total": int(ij.sum()),
            "iters_kron_total": int(ik.sum()),
            "iters_jacobi_max": int(ij.max()),
            "iters_kron_max": int(ik.max()),
            "iter_reduction": 1.0 - ik.sum() / max(ij.sum(), 1),
            "matvec_pairs_jacobi": int(rj.matvec_pairs),
            "matvec_pairs_kron": int(rk.matvec_pairs),
            "us_solve_jacobi": us_j,
            "us_solve_kron": us_k,
            "wallclock_speedup": us_j / max(us_k, 1e-9),
            "values_max_rel_err": vals_err,
        }
        from repro.core.mgk import tile_density
        entry["octile_density"] = max(tile_density(g1), tile_density(g2))
        report["pcg"].append(entry)
        row(f"pcg_{name}_jacobi", us_j, f"iters={int(ij.sum())}")
        row(f"pcg_{name}_kron", us_k,
            f"iters={int(ik.sum())}"
            f",reduction={entry['iter_reduction']:.1%}"
            f",speedup={entry['wallclock_speedup']:.2f}x")

    # bf16 pack streaming: bytes per matvec + measured parity
    g1, g2 = _bucket(B, n, BUCKETS[1][1], seed)
    pf1 = row_panel_packs_for_batch(g1)
    pf2 = row_panel_packs_for_batch(g2)
    pb1 = row_panel_packs_for_batch(g1, pack_dtype=jnp.bfloat16)
    pb2 = row_panel_packs_for_batch(g2, pack_dtype=jnp.bfloat16)
    rng = np.random.default_rng(seed)
    nn = g1.adjacency.shape[1]
    P = to_tiles(jnp.asarray(rng.random((B, nn, nn)).astype(np.float32)),
                 pf1.tile)
    yf = xmv_row_panel_batched(pf1, pf2, P, EK)
    yb = xmv_row_panel_batched(pb1, pb2, P, EK)
    rel = float(np.max(np.abs(np.asarray(yf - yb)))
                / np.max(np.abs(np.asarray(yf))))
    bytes_f32 = _pack_bytes(pf1) + _pack_bytes(pf2)
    bytes_bf16 = _pack_bytes(pb1) + _pack_bytes(pb2)
    report["bf16"] = {
        "bytes_per_matvec_f32": bytes_f32,
        "bytes_per_matvec_bf16": bytes_bf16,
        "bytes_ratio": bytes_f32 / max(bytes_bf16, 1),
        "matvec_max_rel_err": rel,
    }
    row("pack_bytes_f32", float(bytes_f32), "per-matvec value buffers")
    row("pack_bytes_bf16", float(bytes_bf16),
        f"ratio={report['bf16']['bytes_ratio']:.2f}x,err={rel:.1e}")

    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# wrote {out_path}", flush=True)
    return report


if __name__ == "__main__":
    run()
