#!/usr/bin/env python3
"""Smoke run of the main path on one TPU chip: all-pairs Gram builds
through ``GramDriver`` with the Pallas tile kernels compiled for the
chip (not interpreted), each checked against the numpy reference.

    python3 chip_smoke.py             # full size; needs a TPU
    python3 chip_smoke.py --small     # small sets, for a CPU rehearsal
    python3 chip_smoke.py --chips 4   # only the pair-sharded dense step

Phases (one chip):

* ``nws-mxu`` / ``nws-vpu``: the paper's synthetic NWS set (160 graphs x
  96 nodes) as one whole Gram, ``method="pallas_sparse"`` with Gram-tile
  execution on 8x8 tiles, ``sparse_mode="mxu"`` (MXU contraction) and
  ``"elementwise"`` (VPU, what ``"auto"`` runs);
* ``drugbank-dense``: the DrugBank-shaped set, size-bucketed, through
  ``method="pallas"`` (the dense kernel) on every bucket.

Each build is compared with ``core.reference.mgk_direct`` on sampled
pairs and, as a whole, with a ``method="lowrank"`` Gram. Timings printed
here are smoke timings, not benchmark numbers. The last line of standard
output is one JSON object; it is printed only when every check passed on
a TPU, and the exit code is non-zero otherwise.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import contextlib
import json
import os
import shutil
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
WORK = os.path.join(ROOT, ".smoke")          # chunk stores, git-ignored
CACHE = os.path.join(ROOT, ".jax_cache")     # compile cache, git-ignored

REL_TOL = 1e-4        # against mgk_direct and against the lowrank Gram
SHARD_TOL = 1e-5      # 4-chip Gram against the 1-chip Gram
DIRECT_MAX_DIM = 96 * 96   # product systems small enough for LAPACK


def _fail(msg: str, code: int) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(code)


if not os.path.isdir(os.path.join(SRC, "repro")):
    _fail(f"no repro package under {SRC}; run from a checkout", 2)
sys.path.insert(0, SRC)

import numpy as np          # noqa: E402
import jax                  # noqa: E402
from jax.sharding import Mesh   # noqa: E402

from repro.core import KroneckerDelta, SquareExponential   # noqa: E402
from repro.core.reference import mgk_direct               # noqa: E402
from repro.data import bucket_graphs, make_drugbank_like_dataset  # noqa
from repro.data.synthetic import make_synthetic_dataset   # noqa: E402
from repro.distributed import ChunkStore, GramDriver      # noqa: E402
from repro.distributed.gram import gram_pair_step         # noqa: E402

# the kernels of examples/gram_pipeline.py
VK = KroneckerDelta(0.5, 8)
EK = SquareExponential(1.0, rank=12)

# lowering and backend compile; tracing is left out because nested
# jit traces would be counted more than once
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_compile_secs = [0.0]


def _on_duration(event: str, duration: float, **_) -> None:
    # the main thread drives the chip; the reference thread's host
    # compiles are not counted
    if event in _COMPILE_EVENTS and \
            threading.current_thread() is threading.main_thread():
        _compile_secs[0] += duration


def use_compile_cache() -> None:
    """JAX's persistent compile cache: where JAX_COMPILATION_CACHE_DIR
    says (JAX reads the variable itself), else one fixed path inside
    the checkout — the path is part of the cache key."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE)


def emit(phase: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {body}", flush=True)


def one_chip_mesh(devices) -> Mesh:
    return Mesh(np.array(devices[:1]).reshape(1, 1), ("data", "model"))


def timed_build(name: str, ds, mesh, **driver_kw):
    """Run one GramDriver build with a fresh chunk store; returns
    (K, driver, store, build seconds, compile seconds)."""
    path = os.path.join(WORK, name)
    shutil.rmtree(path, ignore_errors=True)
    store = ChunkStore(path)
    drv = GramDriver(ds, mesh, VK, EK, store=store, normalize=False,
                     **driver_kw)
    c0, t0 = _compile_secs[0], time.perf_counter()
    K = drv.run()
    return K, drv, store, time.perf_counter() - t0, _compile_secs[0] - c0


def sample_pairs(ds, n_pairs: int, seed: int) -> list[tuple[int, int]]:
    """Distinct (i <= j) pairs whose product system LAPACK can solve."""
    rng = np.random.default_rng(seed)
    sizes = np.array([g.n_nodes for g in ds.graphs])
    ii, jj = np.triu_indices(len(ds))
    ok = sizes[ii] * sizes[jj] <= DIRECT_MAX_DIM
    pick = rng.choice(int(ok.sum()), size=min(n_pairs, int(ok.sum())),
                      replace=False)
    return [(int(ii[ok][k]), int(jj[ok][k])) for k in pick]


def direct_values(ds, pairs) -> tuple[np.ndarray, float]:
    """mgk_direct on the host: the reference never touches the device
    under test. Returns (values, seconds)."""
    try:
        host = jax.default_device(jax.devices("cpu")[0])
    except RuntimeError:      # no CPU backend configured: default device
        host = contextlib.nullcontext()
    t0 = time.perf_counter()
    with host:
        vals = np.array([mgk_direct(ds.graphs[i], ds.graphs[j], VK, EK)
                         for i, j in pairs])
    return vals, time.perf_counter() - t0


def rel_err(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.abs(b)))


def check_build(name, K, drv, store, secs, csecs, pairs, ref, K_lowrank,
                failures, on_tpu):
    """Print one build's smoke line and record every failed check."""
    ref, ref_secs = ref.result()
    hlo = drv.lower_block(drv.blocks()[0]).compile().as_text()
    custom_call = "tpu_custom_call" in hlo
    its = np.concatenate([store.load_block(b)["iterations"]
                          for b in sorted(store.done_blocks())])
    h = drv.health
    err_direct = rel_err([K[i, j] for i, j in pairs], ref)
    err_lowrank = rel_err(K, K_lowrank)
    emit(name, smoke_compile_s=f"{csecs:.1f}", smoke_build_s=f"{secs:.1f}",
         blocks=len(drv.blocks()),
         iterations=f"{its.min()}/{its.mean():.1f}/{its.max()}",
         max_rel_err_vs_mgk_direct=f"{err_direct:.3e}",
         direct_pairs=len(pairs), smoke_mgk_direct_s=f"{ref_secs:.1f}",
         max_rel_err_vs_lowrank=f"{err_lowrank:.3e}",
         retries=h["retries"], escalations=h["escalations"],
         quarantined=len(h["quarantined_pairs"]),
         nonconverged=h["nonconverged_by_bucket"] or 0,
         tpu_custom_call=custom_call)
    checks = {
        "finite": bool(np.isfinite(K).all()),
        "vs_mgk_direct": err_direct <= REL_TOL,
        "vs_lowrank": err_lowrank <= REL_TOL,
        "no_retries": h["retries"] == 0,
        "no_escalations": h["escalations"] == 0,
        "no_quarantine": not h["quarantined_pairs"],
        "converged": not h["nonconverged_by_bucket"],
    }
    if on_tpu:
        checks["tpu_custom_call"] = custom_call
    failures += [f"{name}: {k}" for k, ok in checks.items() if not ok]


def run_one_chip(small: bool, on_tpu: bool, failures: list) -> None:
    mesh = one_chip_mesh(jax.devices())
    n_pairs = 8 if small else 32
    nws = bucket_graphs(make_synthetic_dataset(
        "nws", 16 if small else 160, 32 if small else 96, seed=0))
    db = make_drugbank_like_dataset(16 if small else 128, seed=0,
                                    max_atoms=40 if small else 551)
    # the dense build buckets by multiples of 16 (one bucket per padded
    # size); the lowrank reference Gram, which does not depend on the
    # bucketing, uses three coarse buckets and so few compiled shapes
    db_dense = bucket_graphs(db, multiple_of=16, max_buckets=12)
    db_ref = bucket_graphs(db, max_buckets=3)
    nws_pairs = sample_pairs(nws, n_pairs, seed=1)
    db_pairs = sample_pairs(db_dense, n_pairs, seed=2)
    # the numpy references run on the host alongside the chip phases;
    # every check waits until all builds are done, so the references
    # never hold up the chip
    pool = concurrent.futures.ThreadPoolExecutor(max_workers=1)
    nws_ref = pool.submit(direct_values, nws, nws_pairs)
    db_ref_vals = pool.submit(direct_values, db_dense, db_pairs)
    builds = []

    # (a) the paper's synthetic set, one whole Gram, Gram-tile execution
    K_lr, drv, _, secs, csecs = timed_build("nws-lowrank", nws, mesh,
                                            method="lowrank")
    emit("nws-lowrank", smoke_compile_s=f"{csecs:.1f}",
         smoke_build_s=f"{secs:.1f}", retries=drv.health["retries"])
    for name, mode in (("nws-mxu", "mxu"), ("nws-vpu", "elementwise")):
        out = timed_build(name, nws, mesh, method="pallas_sparse",
                          gram_tile=True, tile_shape=(8, 8),
                          sparse_mode=mode)
        builds.append((name, *out, nws_pairs, nws_ref, K_lr))

    # (b) DrugBank-shaped, size-bucketed, the dense kernel on every bucket
    emit("drugbank", graphs=len(db),
         buckets=[(b.pad_to, len(b.indices)) for b in db_dense.buckets],
         reference_buckets=[(b.pad_to, len(b.indices))
                            for b in db_ref.buckets])
    K_lr, drv, _, secs, csecs = timed_build(
        "drugbank-lowrank", db_ref, mesh, method="lowrank",
        pairs_per_block=256)
    emit("drugbank-lowrank", smoke_compile_s=f"{csecs:.1f}",
         smoke_build_s=f"{secs:.1f}", retries=drv.health["retries"])
    out = timed_build("drugbank-dense", db_dense, mesh, method="pallas")
    builds.append(("drugbank-dense", *out, db_pairs, db_ref_vals, K_lr))

    for build in builds:
        check_build(*build, failures, on_tpu)
    pool.shutdown()


def run_four_chips(small: bool, failures: list) -> None:
    """The one path across chips: the dense (lowrank) step with the pair
    axis sharded over a (4, 1) ("data", "model") mesh, against the same
    build on one device of the same process."""
    devices = jax.devices()
    if len(devices) != 4:
        _fail(f"--chips 4 needs 4 devices, found {len(devices)}", 3)
    mesh4 = Mesh(np.array(devices).reshape(4, 1), ("data", "model"))
    ds = bucket_graphs(make_synthetic_dataset(
        "nws", 16 if small else 160, 32 if small else 96, seed=0))
    K1, _, _, s1, c1 = timed_build("nws-1dev", ds, one_chip_mesh(devices),
                                   method="lowrank")
    K4, drv, _, s4, c4 = timed_build("nws-4dev", ds, mesh4,
                                     method="lowrank")
    # one block's output shards: the pair axis must really be split
    block = drv.blocks()[0]
    step = gram_pair_step(mesh4, VK, EK, method="lowrank")
    g1 = ds.batch(block.rows, pad_to=block.pad_row)
    g2 = ds.batch(block.cols, pad_to=block.pad_col)
    shards = step(g1, g2).values.addressable_shards
    where = [(str(s.device), s.index[0].start, s.index[0].stop)
             for s in shards]
    err = rel_err(K4, K1)
    emit("nws-4chip", smoke_build_1dev_s=f"{s1:.1f}",
         smoke_compile_1dev_s=f"{c1:.1f}", smoke_build_4dev_s=f"{s4:.1f}",
         smoke_compile_4dev_s=f"{c4:.1f}",
         max_rel_err_4dev_vs_1dev=f"{err:.3e}", block_pairs=block.n_pairs)
    emit("nws-4chip-shards", shards=where)
    if not np.isfinite(K4).all():
        failures.append("4-chip Gram not finite")
    if err > SHARD_TOL:
        failures.append("4-chip Gram differs from 1-chip Gram")
    if len({d for d, _, _ in where}) != 4:
        failures.append("block output not split over 4 devices")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--small", action="store_true",
                    help="small sets for a CPU rehearsal (interpret mode);"
                         " still fails its device check off the TPU")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the pair-sharded step on 4 chips")
    args = ap.parse_args()

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not args.small:
        _fail(f"no TPU: JAX backend is {jax.default_backend()!r}", 3)
    use_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    os.makedirs(WORK, exist_ok=True)

    failures: list[str] = []
    if args.chips == 4:
        run_four_chips(args.small, failures)
    else:
        run_one_chip(args.small, on_tpu, failures)
    if failures:
        _fail("FAILED: " + "; ".join(failures), 1)
    if not on_tpu:
        _fail(f"checks passed, but on {jax.default_backend()!r}, not a"
              f" TPU", 3)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
