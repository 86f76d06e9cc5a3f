#!/usr/bin/env python3
"""Device time of one Gram-tile matvec in each contraction, on the chip.

    python3 benchmarks/contraction_sweep.py [--out chiprun_out/x.json]
    JAX_PLATFORMS=cpu python3 benchmarks/contraction_sweep.py --small

For each octile edge t and each feature-expandable edge kernel (and so
feature rank R), ``xmv_gram_tile`` runs an 8 x 8 Gram tile of NWS graphs
(96 nodes, the paper's synthetic set) in the MXU low-rank contraction
and in the elementwise (VPU) contraction, with the fused diagonal
epilogue as in a PCG solve. The time per matvec is the summed device
duration of the kernel's events in a profiler trace over ``--reps``
calls, not the host clock. ``--small`` runs 2 x 2 tiles of 32-node
graphs in interpret mode: a rehearsal of the control flow, whose times
are no device numbers.

The table this prints is the ground of ``sparse_mode="auto"`` running
the elementwise contraction (``_resolve_mode`` in
``kernels/xmv_block_sparse.py``; DESIGN.md §3.4).
"""
from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import re
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import KroneckerDelta, SquareExponential  # noqa: E402
from repro.core.graph import batch_from_graphs  # noqa: E402
from repro.data.synthetic import make_synthetic_dataset  # noqa: E402
from repro.kernels.ops import row_panel_packs_for_batch  # noqa: E402
from repro.kernels.xmv_block_sparse import xmv_gram_tile  # noqa: E402

KERNEL = "xmv_gram_tile"
_SUFFIX = re.compile(r"[.:]\d+$")

# the repo's feature-expandable edge kernels, by rank: a smaller and a
# larger rank around the NWS cell's SquareExponential(rank = 12)
EDGE_KERNELS = (
    ("SquareExponential(rank=4)", SquareExponential(1.0, rank=4)),
    ("KroneckerDelta(8 labels)", KroneckerDelta(0.5, 8)),
    ("SquareExponential(rank=12)", SquareExponential(1.0, rank=12)),
    ("SquareExponential(rank=24)", SquareExponential(1.0, rank=24)),
)


def _op_family(name: str) -> str:
    return _SUFFIX.sub("", name.split(" = ", 1)[0].lstrip("%"))


def kernel_seconds(trace_dir: str) -> tuple[float, int]:
    """(summed device seconds, event count) of the kernel's events on
    the first TPU in the trace written under ``trace_dir``."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    total, n = 0, 0
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                if _op_family(e.name) == KERNEL:
                    total += e.duration_ns
                    n += 1
    return total / 1e9, n


def edge_labels(graphs, ek) -> list:
    """Integer label codes for KroneckerDelta, the graphs' U[0, 1]
    labels otherwise."""
    if not isinstance(ek, KroneckerDelta):
        return graphs
    out = []
    for g in graphs:
        codes = np.round(np.asarray(g.edge_labels) * (ek.n_labels - 1))
        out.append(dataclasses.replace(
            g, edge_labels=codes.astype(np.float32)
            * (np.asarray(g.adjacency) != 0)))
    return out


def measure(gi, gj, ek, tile: int, mode: str, reps: int, small: bool):
    bi = batch_from_graphs(gi)
    bj = batch_from_graphs(gj)
    pi = row_panel_packs_for_batch(bi, tile=tile, edge_kernel=ek)
    pj = row_panel_packs_for_batch(bj, tile=tile, edge_kernel=ek)
    n, m = bi.adjacency.shape[1], bj.adjacency.shape[1]
    shape = (len(gi), len(gj), n // tile, m // tile, tile, tile)
    rng = np.random.default_rng(0)
    P = jnp.asarray(rng.uniform(0.0, 1.0, shape).astype(np.float32))
    diag = jnp.asarray(rng.uniform(1.0, 2.0, shape).astype(np.float32))

    def call():
        return xmv_gram_tile(pi, pj, P, ek, diag=diag, mode=mode)

    call().block_until_ready()           # compile and warm up
    if small:
        return None
    with tempfile.TemporaryDirectory(dir=os.path.join(ROOT, ".chipwork")) \
            as d:
        with jax.profiler.trace(d):
            for _ in range(reps):
                out = call()
            out.block_until_ready()
        secs, n_events = kernel_seconds(d)
    if n_events != reps:
        print(f"contraction_sweep: {n_events} {KERNEL} events for {reps}"
              f" calls (t = {tile}, {mode})", file=sys.stderr)
    return secs / n_events if n_events else float("nan")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--tiles", default="8,16,32")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "contraction_sweep.json"))
    args = ap.parse_args()
    dev = jax.devices()[0]
    if not args.small and dev.platform != "tpu":
        print(f"contraction_sweep: no TPU ({dev.platform})", file=sys.stderr)
        return 3
    os.makedirs(os.path.join(ROOT, ".chipwork"), exist_ok=True)
    B, n = (2, 32) if args.small else (8, 96)
    graphs = make_synthetic_dataset("nws", 2 * B, n, seed=0)
    rows = []
    for tile in map(int, args.tiles.split(",")):
        for name, ek in EDGE_KERNELS:
            gs = edge_labels(graphs, ek)
            times = {mode: measure(gs[:B], gs[B:], ek, tile, mode,
                                   args.reps, args.small)
                     for mode in ("mxu", "elementwise")}
            row = {"tile": tile, "edge_kernel": name,
                   "rank": ek.feature_rank(),
                   "mxu_ms": None if args.small else 1e3 * times["mxu"],
                   "elementwise_ms": None if args.small
                   else 1e3 * times["elementwise"]}
            if not args.small:
                row["mxu_over_elementwise"] = times["mxu"] / \
                    times["elementwise"]
            rows.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": {"platform": dev.platform,
                              "kind": dev.device_kind},
                   "pairs": B * B, "n_nodes": n, "reps": args.reps,
                   "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
