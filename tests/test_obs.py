"""The program's spans and counters (repro/obs.py): tiny Gram builds
on the CPU, traced with the JAX profiler, read back from the
``.xplane.pb`` and checked against counts known from the blocks."""
import glob

import numpy as np
import pytest
import jax
from jax.profiler import ProfileData
from jax.sharding import Mesh

from repro import obs
from repro.core import KroneckerDelta, SquareExponential, pad_graphs
from repro.data import bucket_graphs, make_drugbank_like_dataset
from repro.distributed import ChunkStore, GramDriver
from repro.distributed.gram import GraphPackCache
from repro.distributed.faults import FaultInjector, FaultPlan

VK = KroneckerDelta(0.5, n_labels=8)
EK = SquareExponential(1.0, rank=10)

# "gram-tile" streams bf16 packs (the benchmark's bf16-packs control),
# "gram-tile-vpu" f32 packs; both pin the octile edge at 8.
# "gram-tile-edge" leaves the edge to the step: t = 16 on the 16-node
# bucket
CASES = {
    "lowrank": dict(method="lowrank", pairs_per_block=3),
    "gram-tile": dict(method="pallas_sparse", gram_tile=True,
                      tile_shape=(2, 2), pack_dtype="bfloat16", tile=8),
    "gram-tile-vpu": dict(method="pallas_sparse", gram_tile=True,
                          tile_shape=(2, 2), tile=8),
    "gram-tile-edge": dict(method="pallas_sparse", gram_tile=True,
                           tile_shape=(2, 2)),
}
# spans every block loop opens; the sparse path adds its pack stages
COMMON = {"mgk.build", "mgk.block", "mgk.batch", "mgk.dispatch",
          "mgk.readback", "mgk.save", "mgk.load", "mgk.assemble"}
SPANS = {"lowrank": COMMON,
         "gram-tile": COMMON | {"mgk.stack", "mgk.pack"},
         "gram-tile-vpu": COMMON | {"mgk.stack", "mgk.pack"},
         "gram-tile-edge": COMMON | {"mgk.stack", "mgk.pack"}}
# spans of one block's work, each inside that block's mgk.block
IN_BLOCK = {"mgk.batch", "mgk.dispatch", "mgk.readback", "mgk.save",
            "mgk.stack", "mgk.pack", "mgk.retry"}


def _dataset():
    """Five graphs padded to one bucket: 2 x 2 Gram tiles include
    ragged ones, and 3-pair blocks end on a partial block."""
    gs = [g for g in make_drugbank_like_dataset(40, seed=5)
          if 9 <= g.n_nodes <= 16][:5]
    return bucket_graphs(gs, max_buckets=1)


def _mesh():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))


def _spans(trace_dir) -> list[tuple[str, int, int, dict]]:
    """``(name, start_ns, end_ns, stats)`` of every ``mgk.`` span."""
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats)) for e in line.events
                           if e.name.startswith("mgk."))
    return out


def _build(ds, tmp, name, traced, faults=None, **kw):
    """One whole build on a fresh driver and store: (Gram, counter
    deltas, driver, mgk spans or None)."""
    drv = GramDriver(ds, _mesh(), VK, EK, store=ChunkStore(str(tmp / name)),
                     faults=faults, **kw)
    before = obs.counters()
    if traced:
        with jax.profiler.trace(str(tmp / f"{name}.trace")):
            K = drv.run()
    else:
        K = drv.run()
    spent = obs.delta(before)
    assert drv.health["counters"] == spent
    return K, spent, drv, _spans(tmp / f"{name}.trace") if traced else None


@pytest.fixture(scope="module")
def builds(tmp_path_factory):
    """Per case: an untraced build, then a traced one on a new driver
    (its pack cache starts empty, so the trace holds the misses)."""
    tmp = tmp_path_factory.mktemp("obs")
    ds = _dataset()
    out = {}
    for case, kw in CASES.items():
        plain, _, _, _ = _build(ds, tmp, f"{case}-plain", False, **kw)
        K, spent, drv, spans = _build(ds, tmp, case, True, **kw)
        out[case] = dict(ds=ds, plain=plain, K=K, spent=spent, drv=drv,
                         spans=spans)
    return out


@pytest.mark.parametrize("case", list(CASES))
def test_every_span_is_recorded(builds, case):
    names = {s[0] for s in builds[case]["spans"]}
    assert names == SPANS[case]


@pytest.mark.parametrize("case", list(CASES))
def test_block_spans_nest_in_their_block(builds, case):
    b = builds[case]
    spans = b["spans"]
    (build,) = [s for s in spans if s[0] == "mgk.build"]
    blocks = [s for s in spans if s[0] == "mgk.block"]
    assert sorted(s[3]["block"] for s in blocks) == \
        sorted(blk.block_id for blk in b["drv"].blocks())
    for s in blocks:
        assert build[1] <= s[1] and s[2] <= build[2]
    for s in spans:
        if s[0] in IN_BLOCK:
            owners = [blk for blk in blocks
                      if blk[1] <= s[1] and s[2] <= blk[2]]
            assert len(owners) == 1, s
    # every block batches both its sides and dispatches, reads back and
    # saves once
    for blk in blocks:
        inside = [s[0] for s in spans
                  if blk[1] <= s[1] and s[2] <= blk[2]]
        assert inside.count("mgk.batch") == 2
        for name in ("mgk.dispatch", "mgk.readback", "mgk.save"):
            assert inside.count(name) == 1


@pytest.mark.parametrize("case", list(CASES))
def test_tracing_leaves_the_values_bit_identical(builds, case):
    np.testing.assert_array_equal(builds[case]["K"], builds[case]["plain"])


def _stored(drv) -> dict:
    return {bid: drv.store.load_block(bid)
            for bid in drv.store.done_blocks()}


@pytest.mark.parametrize("case", list(CASES))
def test_matvec_pairs_is_the_lockstep_work(builds, case):
    """Classic lockstep PCG runs every pair of a block until its slowest
    converges: B x (the block's largest iteration count)."""
    b = builds[case]
    want = sum(len(r["iterations"]) * int(r["iterations"].max())
               for r in _stored(b["drv"]).values())
    assert b["spent"]["matvec_pairs"] == want


def _batch_bytes(ds, idx, pad) -> int:
    arrs = pad_graphs([ds.graphs[i] for i in idx], pad_to=pad)
    return sum(v.nbytes for v in arrs.values())


def test_lowrank_counts_batches_and_one_read_per_block(builds):
    b = builds["lowrank"]
    blocks = b["drv"].blocks()
    assert b["spent"]["h2d_bytes"] == sum(
        _batch_bytes(b["ds"], blk.rows, blk.pad_row)
        + _batch_bytes(b["ds"], blk.cols, blk.pad_col) for blk in blocks)
    assert b["spent"]["host_syncs"] == len(blocks)
    assert "pack_cache.miss" not in b["spent"]


def _check_gram_tile_counts(b, tile=8, pack_dtype=None):
    """Pack-cache lookups, blocking reads and bytes sent of a Gram-tile
    build, counted from its blocks; ``tile`` is the octile edge every
    block ran at, ``pack_dtype`` the packs' stored dtype. Returns the
    number of blocks."""
    ds, blocks = b["ds"], b["drv"].blocks()
    axes = [(np.unique(blk.rows), np.unique(blk.cols), blk.pad_row)
            for blk in blocks]
    graphs = {int(i) for r, c, _ in axes for i in np.concatenate([r, c])}
    lookups = sum(len(r) + len(c) for r, c, _ in axes)
    assert b["spent"]["pack_cache.miss"] == len(graphs)
    assert b["spent"]["pack_cache.hit"] == lookups - len(graphs)
    # two slices per graph of an axis, one readback
    assert b["spent"]["host_syncs"] == sum(
        2 * (len(r) + len(c)) + 1 for r, c, _ in axes)
    # both pair batches of each block, then each axis's stacked pack
    cache = GraphPackCache(tile=tile, pack_dtype=pack_dtype)
    packs = 0
    for (r, c, pad), blk in zip(axes, blocks):
        for idx in (r, c):
            pack = cache.stacked_axis(idx, ds.batch(idx, pad_to=pad))
            packs += sum(x.nbytes for x in pack)
    assert b["spent"]["h2d_bytes"] == packs + sum(
        _batch_bytes(ds, blk.rows, blk.pad_row)
        + _batch_bytes(ds, blk.cols, blk.pad_col) for blk in blocks)
    return len(blocks)


def test_gram_tile_counts_packs_slices_and_label_checks(builds):
    """The bf16-pack step: half the pack bytes of f32 packs sent, and no
    label check, so no label reads beyond the axis slices."""
    b = builds["gram-tile"]
    n_blocks = _check_gram_tile_counts(b, pack_dtype="bfloat16")
    assert b["spent"]["xmv.octile_edge.8"] == n_blocks


def test_gram_tile_elementwise_counts_packs_and_slices(builds):
    """The f32-pack step: adjacency and label tiles sent once per axis
    graph per block, and no contraction counter (the step has one
    contraction)."""
    b = builds["gram-tile-vpu"]
    n_blocks = _check_gram_tile_counts(b)
    assert b["spent"]["xmv.octile_edge.8"] == n_blocks
    assert not [k for k in b["spent"] if k.startswith("xmv.contraction.")]


def test_octile_edge_counts_once_per_block_solve(builds):
    """Every block solve counts the octile edge it ran at, and the count
    reaches ``health["counters"]``: t = 8 where the edge is pinned, the
    step's pick (t = 16 on this 16-node bucket) where it is not, with
    that edge's packs and bytes."""
    for case in ("gram-tile", "gram-tile-vpu"):
        b = builds[case]
        edges = {k: v for k, v in b["drv"].health["counters"].items()
                 if k.startswith("xmv.octile_edge.")}
        assert edges == {"xmv.octile_edge.8": len(b["drv"].blocks())}
    b = builds["gram-tile-edge"]
    n_blocks = _check_gram_tile_counts(b, tile=16)
    edges = {k: v for k, v in b["drv"].health["counters"].items()
             if k.startswith("xmv.octile_edge.")}
    assert edges == {"xmv.octile_edge.16": n_blocks}
    assert b["drv"]._pack_cache.edges == {(16, 16): 16}


def test_gradient_build_counts_its_octile_edge(tmp_path):
    """The gradient Gram-tile build takes the same edge as the forward
    one and counts it once per block solve."""
    ds = _dataset()
    drv = GramDriver(ds, _mesh(), VK, EK, store=None,
                     **CASES["gram-tile-edge"])
    drv.run_with_grad()
    edges = {k: v for k, v in drv.health["counters"].items()
             if k.startswith("xmv.octile_edge.")}
    assert edges == {"xmv.octile_edge.16": len(drv.blocks())}


def test_a_retried_block_opens_a_retry_span(tmp_path):
    """A transient matvec fault on every block's first attempt: each
    block heals by one retry at the base rung, in an ``mgk.retry`` span
    inside its block."""
    ds = _dataset()
    faults = FaultInjector(FaultPlan(matvec_nan_fraction=1.0))
    K, spent, drv, spans = _build(ds, tmp_path, "retry", True,
                                  faults=faults, **CASES["lowrank"])
    n_blocks = len(drv.blocks())
    assert drv.health["retries"] == n_blocks
    retries = [s for s in spans if s[0] == "mgk.retry"]
    assert [s[3]["rung"] for s in retries] == [0] * n_blocks
    blocks = [s for s in spans if s[0] == "mgk.block"]
    for s in retries:
        assert any(blk[1] <= s[1] and s[2] <= blk[2] for blk in blocks)
    # the retry reads back its own result: two reads per block
    assert spent["host_syncs"] == 2 * n_blocks


def test_counters_are_monotonic_snapshots():
    before = obs.counters()
    obs.count("test.obs", 3)
    obs.count("test.obs")
    assert obs.delta(before) == {"test.obs": 4}
    assert obs.counters()["test.obs"] - before.get("test.obs", 0) == 4
    before["test.obs"] = -1            # a snapshot is a copy
    assert obs.counters()["test.obs"] != -1
