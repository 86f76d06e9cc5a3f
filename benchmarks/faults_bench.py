"""PR 6 robustness tracking: self-healing Gram builds under faults.

Emits ``BENCH_faults.json`` with two sections:

* **guard** — the cost of the per-pair PCG numerical guards
  (core/pcg.py) on the CLEAN hot path: the same bucket solved at a
  FIXED trip count with ``guard=True`` vs ``guard=False``, so both arms
  execute identical matvec work and the difference is pure guard
  arithmetic (a handful of [B] scalar checks per iteration). CI asserts
  this overhead stays < 5% — the guards are meant to be always-on.
* **campaign** — a full Gram build driven through the seeded fault
  campaign (``distributed/faults.py``: mid-build driver kill, chunk
  corruption + truncation on disk, injected matvec NaNs, forced
  kron-certificate failure) versus a fault-free build of the same
  dataset. Asserts the healed result is BITWISE-IDENTICAL to the clean
  one with zero NaN entries, reports the injection ledger, restart
  count, retry/escalation totals, and the wall-clock recovery overhead
  (the price of recomputing faulted blocks — informational, it scales
  with the injected fault rate, not with code quality).

Numbers come from the CPU/interpret harness: absolute times are not
TPU times, but the guard-overhead RATIO is arithmetic the accelerator
sees too (same guard ops per iteration), and bitwise identity is exact.
"""
from __future__ import annotations

import json
import os
import shutil
import tempfile
import time

import numpy as np

import jax
from jax.sharding import Mesh

from repro.core import KroneckerDelta, SquareExponential
from repro.core.mgk import mgk_pairs_sparse
from repro.data import bucket_graphs, make_drugbank_like_dataset
from repro.distributed import ChunkStore, FaultPlan, GramDriver, \
    run_campaign
from repro.kernels.ops import row_panel_packs_for_batch
from .common import row, time_fn

VK = KroneckerDelta(0.5, n_labels=8)
EK = SquareExponential(1.0, rank=10)

# the campaign every build must heal from (seeded => reproducible):
# roughly half the blocks see a transient matvec NaN, a third get their
# chunk corrupted on disk, plus truncation, forced certificate failure
# and one mid-build driver kill
CAMPAIGN = FaultPlan(seed=3, kill_after_blocks=3, corrupt_fraction=0.3,
                     truncate_fraction=0.2, matvec_nan_fraction=0.5,
                     cert_fail_fraction=0.4)


def _dataset(n_graphs: int, seed: int):
    gs = [g for g in make_drugbank_like_dataset(n_graphs + 8, seed=seed)
          if g.n_nodes >= 4][:n_graphs]
    return bucket_graphs(gs, max_buckets=3)


def _mesh() -> Mesh:
    return Mesh(np.array(jax.devices()).reshape(1, 1), ("data", "model"))


def _guard_overhead(report: dict, B: int, seed: int, fixed_iters: int,
                    iters: int) -> None:
    """guard=True vs guard=False at a fixed trip count on one bucket."""
    gs = []
    for s in range(seed, seed + 50):
        gs += [g for g in make_drugbank_like_dataset(4 * B, seed=s)
               if 6 <= g.n_nodes <= 24]
        if len(gs) >= 2 * B:
            break
    gs = gs[:2 * B]
    from repro.core.graph import batch_from_graphs
    pad = max(g.n_nodes for g in gs)
    pad += (-pad) % 8
    g1 = batch_from_graphs(gs[:B], pad_to=pad)
    g2 = batch_from_graphs(gs[B:2 * B], pad_to=pad)
    p1 = row_panel_packs_for_batch(g1)
    p2 = row_panel_packs_for_batch(g2)

    def solve(guard):
        return mgk_pairs_sparse(g1, g2, p1, p2, VK, EK,
                                fixed_iters=fixed_iters,
                                guard=guard)

    # identical trip counts => identical matvec work in both arms
    r_on, r_off = solve(True), solve(False)
    np.testing.assert_allclose(np.asarray(r_on.values),
                               np.asarray(r_off.values), rtol=1e-6)
    us_off = time_fn(lambda: solve(False).values.block_until_ready(),
                     iters=iters)
    us_on = time_fn(lambda: solve(True).values.block_until_ready(),
                    iters=iters)
    overhead = us_on / max(us_off, 1e-9) - 1.0
    report["guard"] = {
        "B": B, "fixed_iters": fixed_iters,
        "us_unguarded": us_off, "us_guarded": us_on,
        "overhead": overhead,
    }
    row("guard_off", us_off, f"fixed_iters={fixed_iters}")
    row("guard_on", us_on, f"overhead={overhead:+.1%}")


def _campaign(report: dict, n_graphs: int, pairs_per_block: int,
              seed: int) -> None:
    """Clean build vs the same build through the fault campaign."""
    tmp = tempfile.mkdtemp(prefix="faults_bench_")
    try:
        ds = _dataset(n_graphs, seed)
        mesh = _mesh()

        def driver(store_dir, injector=None):
            return GramDriver(ds, mesh, VK, EK,
                              store=ChunkStore(store_dir),
                              method="pallas_sparse", precond="kron",
                              pairs_per_block=pairs_per_block,
                              faults=injector)

        # warm the jit caches so both timed arms pay only solve time
        driver(os.path.join(tmp, "warm")).run()

        t0 = time.perf_counter()
        clean_driver = driver(os.path.join(tmp, "clean"))
        K_clean = clean_driver.run()
        t_clean = time.perf_counter() - t0

        t0 = time.perf_counter()
        K_fault, rep = run_campaign(
            lambda inj: driver(os.path.join(tmp, "faulty"), inj),
            CAMPAIGN)
        t_fault = time.perf_counter() - t0

        identical = bool(np.array_equal(K_clean, K_fault))
        n_nan = int(np.isnan(K_fault).sum())
        health = rep["health"]
        store = ChunkStore(os.path.join(tmp, "faulty"))
        recovered = {bid: entry for bid, entry in
                     ((b, store.block_entry(b))
                      for b in store.done_blocks())
                     if entry and "recovery" in entry}
        report["campaign"] = {
            "n_graphs": n_graphs, "pairs_per_block": pairs_per_block,
            "seed": CAMPAIGN.seed,
            "restarts": rep["restarts"],
            "injections": rep["injections"],
            "retries": health.get("retries", 0),
            "escalations": health.get("escalations", 0),
            "quarantined_pairs": health.get("quarantined_pairs", []),
            "recovered_blocks_in_manifest": sorted(recovered),
            "bitwise_identical": identical,
            "nan_entries": n_nan,
            "s_clean": t_clean, "s_faulted": t_fault,
            "recovery_overhead": t_fault / max(t_clean, 1e-9) - 1.0,
        }
        assert identical, \
            "faulted build is NOT bitwise-identical to the clean build"
        assert n_nan == 0, f"{n_nan} silent NaN entries in healed Gram"
        row("gram_clean", t_clean * 1e6,
            f"blocks={len(store.done_blocks())}")
        row("gram_faulted", t_fault * 1e6,
            f"restarts={rep['restarts']}"
            f",inj={sum(rep['injections'].values())}"
            f",overhead={report['campaign']['recovery_overhead']:+.1%}"
            f",identical={identical}")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run(out_path: str = "BENCH_faults.json", n_graphs: int = 8,
        pairs_per_block: int = 8, B: int = 4, fixed_iters: int = 32,
        iters: int = 5, seed: int = 7) -> dict:
    report: dict = {}
    _guard_overhead(report, B, seed, fixed_iters, iters)
    _campaign(report, n_graphs, pairs_per_block, seed)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# wrote {out_path}", flush=True)
    return report


if __name__ == "__main__":
    run()
