"""Distributed runtime: scheduling, checkpoint/restart, elasticity,
end-to-end Gram driver."""
import json
import os

import numpy as np
import pytest
import jax
from jax.sharding import Mesh

from repro import obs
from repro.core import KroneckerDelta, SquareExponential
from repro.data import bucket_graphs, make_drugbank_like_dataset, \
    pair_blocks
from repro.distributed import ChunkStore, GramDriver, make_plan, replan
from repro.distributed.checkpoint import load_array_checkpoint, \
    save_array_checkpoint

VK = KroneckerDelta(0.5, n_labels=8)
EK = SquareExponential(1.0, rank=10)


def _dataset(n=10, seed=7):
    gs = [g for g in make_drugbank_like_dataset(n + 6, seed=seed)
          if g.n_nodes >= 4][:n]
    return bucket_graphs(gs, max_buckets=3)


def _mesh():
    return Mesh(np.array(jax.devices()).reshape(1, 1), ("data", "model"))


def test_pair_blocks_cover_all_pairs_once():
    ds = _dataset(12)
    blocks = list(pair_blocks(ds, pairs_per_block=7))
    seen = set()
    for b in blocks:
        for r, c in zip(b.rows, b.cols):
            key = (min(r, c), max(r, c))
            assert key not in seen, key
            seen.add(key)
    n = len(ds)
    assert len(seen) == n * (n + 1) // 2


def test_plan_balances_load():
    ds = _dataset(16)
    blocks = list(pair_blocks(ds, pairs_per_block=4))
    plan = make_plan(blocks, n_groups=4)
    assert plan.makespan_ratio < 1.5
    assigned = [b for q in plan.assignment for b in q]
    assert sorted(assigned) == sorted(b.block_id for b in blocks)


def test_replan_is_elastic_and_deterministic():
    ds = _dataset(12)
    blocks = list(pair_blocks(ds, pairs_per_block=4))
    done = {blocks[0].block_id, blocks[1].block_id}
    p4a = replan(blocks, done, 4)
    p4b = replan(blocks, done, 4)
    assert p4a == p4b                       # deterministic
    p2 = replan(blocks, done, 2)            # shrink fleet
    ids4 = {b for q in p4a.assignment for b in q}
    ids2 = {b for q in p2.assignment for b in q}
    assert ids4 == ids2                     # same remaining work
    assert not ids4 & done


def test_chunk_store_crc_detects_corruption(tmp_path):
    store = ChunkStore(str(tmp_path))
    store.save_block(0, rows=np.array([0]), cols=np.array([1]),
                     values=np.array([0.5]), iterations=np.array([3]))
    blk = store.load_block(0)
    assert blk["values"][0] == 0.5
    # corrupt the file
    with open(store.block_path(0), "r+b") as f:
        f.seek(10)
        f.write(b"\xde\xad")
    with pytest.raises(IOError):
        store.load_block(0)


def test_chunk_store_first_writer_wins(tmp_path):
    store = ChunkStore(str(tmp_path))
    assert store.save_block(3, rows=np.array([0]), cols=np.array([1]),
                            values=np.array([1.0]),
                            iterations=np.array([1]))
    # straggler duplicate must be a no-op
    assert not store.save_block(3, rows=np.array([0]), cols=np.array([1]),
                                values=np.array([9.9]),
                                iterations=np.array([1]))
    assert store.load_block(3)["values"][0] == 1.0


def test_gram_driver_end_to_end_and_restart(tmp_path):
    ds = _dataset(8)
    store = ChunkStore(str(tmp_path))
    drv = GramDriver(ds, _mesh(), VK, EK, store=store, pairs_per_block=8)
    K = drv.run()
    assert K.shape == (8, 8)
    assert not np.isnan(K).any()
    assert np.allclose(K, K.T, atol=1e-6)
    assert np.allclose(np.diag(K), 1.0, atol=1e-5)   # normalized
    w = np.linalg.eigvalsh(K)
    assert w.min() > -1e-6
    done_before = store.done_blocks()
    K2 = drv.run()                                   # restart: no recompute
    assert store.done_blocks() == done_before
    np.testing.assert_allclose(K, K2)


def test_gram_driver_resumes_partial(tmp_path):
    ds = _dataset(8)
    store = ChunkStore(str(tmp_path))
    drv = GramDriver(ds, _mesh(), VK, EK, store=store, pairs_per_block=8)
    blocks = drv.blocks()
    # simulate a crash: precompute only the first block then "restart"
    from repro.distributed.gram import gram_pair_step, solve_pair_block
    step = gram_pair_step(_mesh(), VK, EK)
    out = solve_pair_block(ds, blocks[0], step, 1)
    store.save_block(blocks[0].block_id, **out)
    K = drv.run()       # must complete the remaining blocks
    assert not np.isnan(K).any()


def test_sparse_step_caches_packs_per_graph(monkeypatch):
    """A graph appearing in many pair blocks must be octile-decomposed
    once per bucket size, not once per block (the GraphPackCache)."""
    import repro.core.octile as octile_mod
    from repro.distributed.gram import gram_pair_step, solve_pair_block

    ds = _dataset(8)
    blocks = list(pair_blocks(ds, pairs_per_block=4))
    calls = {"n": 0}
    real_decompose = octile_mod.octile_decompose

    def counting(*a, **kw):
        calls["n"] += 1
        return real_decompose(*a, **kw)

    monkeypatch.setattr(octile_mod, "octile_decompose", counting)
    step = gram_pair_step(_mesh(), VK, EK, method="pallas_sparse")
    assert getattr(step, "wants_indices", False)
    before = obs.counters()
    outs = [solve_pair_block(ds, b, step, 1) for b in blocks]
    # every (graph, bucket pad) combination decomposed exactly once, plus
    # at most one dummy pack per pad size — far below once-per-block
    distinct = {(int(i), b.pad_row) for b in blocks for i in b.rows} | \
               {(int(i), b.pad_col) for b in blocks for i in b.cols}
    assert calls["n"] <= len(distinct) + len(
        {b.pad_row for b in blocks} | {b.pad_col for b in blocks})
    assert obs.delta(before).get("pack_cache.hit", 0) > 0
    # and the cached path computes the same values as the dense reference
    from repro.distributed.gram import gram_pair_step as gps
    ref_step = gps(_mesh(), VK, EK, method="lowrank")
    for b, out in zip(blocks[:2], outs[:2]):
        ref = solve_pair_block(ds, b, ref_step, 1)
        np.testing.assert_allclose(out["values"], ref["values"],
                                   rtol=1e-4)


def test_sparse_step_domain_guard_falls_back_to_elementwise():
    """sparse_mode='auto' must not use the Taylor expansion outside its
    accuracy domain: the block runs exact elementwise, as "auto" does
    for every block, and matches the elementwise reference."""
    from repro.distributed.gram import gram_pair_step, solve_pair_block
    ds = _dataset(6)
    blocks = list(pair_blocks(ds, pairs_per_block=6))
    ek = SquareExponential(1.0, rank=10, domain=0.0)   # always out of domain
    step = gram_pair_step(_mesh(), VK, ek, method="pallas_sparse")
    before = obs.counters()
    out = solve_pair_block(ds, blocks[0], step, 1)
    assert obs.delta(before).get("xmv.contraction.elementwise") == 1
    assert step.pack_cache.edge_kernel is None
    ref_step = gram_pair_step(_mesh(), VK, ek, method="elementwise")
    ref = solve_pair_block(ds, blocks[0], ref_step, 1)
    np.testing.assert_allclose(out["values"], ref["values"], rtol=1e-4)


@pytest.mark.parametrize("mode", ["auto", "mxu"])
def test_sparse_driver_contraction_and_counters(mode):
    """Under "auto" the Gram-tile driver runs the elementwise
    contraction: the pack cache holds no weighted operands and every block solve counts
    ``xmv.contraction.elementwise``. An explicit "mxu" builds the weights
    and counts ``xmv.contraction.mxu``. Either way the Gram matches the
    direct dense solve."""
    from repro.core.reference import mgk_direct
    ds = _dataset(5)
    drv = GramDriver(ds, _mesh(), VK, EK, method="pallas_sparse",
                     gram_tile=True, tile_shape=(2, 2), sparse_mode=mode,
                     normalize=False, tol=1e-10)
    K = drv.run()
    want = "elementwise" if mode == "auto" else "mxu"
    contractions = {k: v for k, v in drv.health["counters"].items()
                    if k.startswith("xmv.contraction.")}
    assert contractions == {f"xmv.contraction.{want}": len(drv.blocks())}
    packs = list(drv._pack_cache._packs.values())
    assert packs and all((p["values_w"] is None) == (mode == "auto")
                         for p in packs)
    for i, j in [(0, 0), (0, 3), (2, 4)]:
        ref = mgk_direct(ds.graphs[i], ds.graphs[j], VK, EK)
        assert K[i, j] == pytest.approx(ref, rel=1e-4)


def test_pack_cache_lru_eviction_roundtrip():
    """The LRU bound must evict oldest entries, and eviction + re-pack
    must round-trip bit-identically (a pack is a pure function of the
    graph arrays); pack-time stats persist across eviction."""
    from repro.core.graph import batch_from_graphs
    from repro.distributed.gram import GraphPackCache
    gs = [g for g in make_drugbank_like_dataset(12, seed=3)
          if 6 <= g.n_nodes <= 32][:4]
    batch = batch_from_graphs(gs, pad_to=32)
    one = lambda b: jax.tree.map(lambda x: x[b:b + 1], batch)  # noqa

    cache = GraphPackCache(tile=8, edge_kernel=EK, max_entries=2)
    first = cache.stacked(np.array([0]), one(0))
    for b in (1, 2, 3):          # push graph 0 out of the LRU window
        cache.stacked(np.array([b]), one(b))
    assert len(cache._packs) == 2
    assert (0, 32, 8) not in cache._packs       # evicted...
    assert cache.density(0, 32) is not None     # ...stats persist
    before = obs.counters()
    again = cache.stacked(np.array([0]), one(0))
    assert obs.delta(before).get("pack_cache.miss") == 1   # re-packed
    for a, b in zip(first, again):
        if a is None:
            assert b is None
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_plan_uses_measured_density_and_iterations(tmp_path):
    """The scheduler satellite: after blocks complete, plan() must feed
    the pack cache's measured octile occupancy and the store's observed
    iteration counts into estimate_cost (not the uniform defaults)."""
    from repro.distributed.scheduler import estimate_cost
    ds = _dataset(8)
    store = ChunkStore(str(tmp_path))
    drv = GramDriver(ds, _mesh(), VK, EK, store=store,
                     method="pallas_sparse", gram_tile=True,
                     tile_shape=(3, 3))
    drv.run()
    blocks = drv.blocks()
    densities = drv._block_densities(blocks)
    iters = drv._block_iters(blocks, store.done_blocks())
    assert densities and iters
    # graphs are sparse: measured occupancy must be below the uniform
    # assumption, and iteration predictions must be real CG counts
    assert all(0.0 < d <= 1.0 for d in densities.values())
    assert any(d < 1.0 for d in densities.values())
    assert all(it >= 1.0 for it in iters.values())
    bid = blocks[0].block_id
    refined = estimate_cost(blocks[0], densities[bid], iters[bid])
    assert refined != estimate_cost(blocks[0])   # defaults overridden
    # a fully-done plan is empty but the wiring must not error
    plan = drv.plan()
    assert plan.assignment == tuple([()] * plan.n_groups) or \
        plan.makespan_ratio >= 1.0


def test_gram_tile_driver_matches_per_pair_driver():
    ds = _dataset(7)
    ref = GramDriver(ds, _mesh(), VK, EK, method="pallas_sparse",
                     pairs_per_block=6).run()
    for kw in (dict(), dict(segment_size=8)):
        gt = GramDriver(ds, _mesh(), VK, EK, method="pallas_sparse",
                        gram_tile=True, tile_shape=(3, 3), **kw).run()
        np.testing.assert_allclose(gt, ref, rtol=1e-4, atol=1e-6)


def _nws_axis(n_nodes, indices):
    """(indices, their batch) of NWS graphs with ``n_nodes`` nodes: one
    axis of a Gram-tile block."""
    from repro.core.graph import batch_from_graphs
    from repro.data import make_synthetic_dataset
    gs = make_synthetic_dataset("nws", n_graphs=len(indices),
                                n_nodes=n_nodes, seed=n_nodes)
    return np.asarray(indices), batch_from_graphs(gs, pad_to=n_nodes)


@pytest.mark.parametrize("pads,tile,want", [
    ((96, 96), None, 32),
    ((48, 48), None, 16),
    ((24, 24), None, 8),
    ((64, 232), None, 8),     # 8 is the largest edge dividing both
    ((256, 256), None, 16),   # the t = 32 envelope is over budget
    ((512, 512), None, None),  # no edge fits: the per-pair route at 8
    ((96, 96), 8, 8),
    ((96, 96), 16, 16),
])
def test_gram_tile_octile_edge(pads, tile, want):
    """The edge a Gram-tile block packs at: under ``tile=None`` the
    largest of (32, 16, 8) that divides both pads and whose kernel
    envelope fits the VMEM budget; an explicit ``tile`` as given.
    Packing only, no kernel runs."""
    from repro.distributed.gram import GraphPackCache, _gram_tile_packs, \
        _GRAM_TILE_VMEM_BUDGET
    from repro.kernels.xmv_block_sparse import gram_tile_vmem_bytes
    cache = GraphPackCache(tile=8 if tile is None else tile)
    rows, cols = _nws_axis(pads[0], [0, 1]), _nws_axis(pads[1], [2, 3])
    packs = _gram_tile_packs(cache, tile, False, rows, cols)
    if want is None:
        assert packs is None and cache.tile == 8
        return
    p1, p2 = packs
    assert (p1.tile, p2.tile) == (want, want)
    assert (p1.n_tile_rows * want, p2.n_tile_rows * want) == pads
    assert gram_tile_vmem_bytes(p1, p2, False) <= _GRAM_TILE_VMEM_BUDGET
    # the stats of every edge tried are kept under that edge
    for t in (32, 16, 8):
        tried = (tile is None and t >= want and not pads[0] % t
                 and not pads[1] % t) or t == tile
        assert (cache.density(0, pads[0], t) is not None) == tried


@pytest.fixture(scope="module")
def nws32():
    """Four 32-node NWS graphs, their Gram at the picked edge (2 x 2
    Gram tiles) and the normalised direct-solve reference."""
    from repro.core.reference import mgk_direct
    from repro.data import make_synthetic_dataset
    ds = bucket_graphs(make_synthetic_dataset("nws", n_graphs=4,
                                              n_nodes=32, seed=3),
                       max_buckets=1)
    n = len(ds)
    ref = np.array([[mgk_direct(ds.graphs[i], ds.graphs[j], VK, EK)
                     for j in range(n)] for i in range(n)])
    d = np.sqrt(np.diag(ref))
    drv = GramDriver(ds, _mesh(), VK, EK, method="pallas_sparse",
                     gram_tile=True, tile_shape=(2, 2), tol=1e-10)
    return ds, drv.run(), ref / d[:, None] / d[None, :], drv


def test_picked_edge_gram_matches_direct(nws32):
    """The step picks t = 32 on the 32-node bucket, and its normalised
    Gram matches the direct solve."""
    _, K, ref, drv = nws32
    assert drv.health["counters"]["xmv.octile_edge.32"] == \
        len(drv.blocks())
    np.testing.assert_allclose(K, ref, rtol=1e-4)


@pytest.mark.parametrize("tile", [8, 16, 32])
def test_gram_is_the_same_at_every_octile_edge(nws32, tile):
    """A pinned edge gives the picked edge's normalised Gram: t changes
    only the summation order."""
    ds, K, _, _ = nws32
    Kt = GramDriver(ds, _mesh(), VK, EK, method="pallas_sparse",
                    gram_tile=True, tile_shape=(2, 2), tol=1e-10,
                    tile=tile).run()
    np.testing.assert_allclose(Kt, K, rtol=0, atol=1e-6)


def test_gradient_gram_at_the_picked_edge(nws32):
    """run_with_grad at the picked edge (t = 32) matches the gradient
    Gram pinned at t = 8."""
    ds = nws32[0]
    base = dict(method="pallas_sparse", gram_tile=True,
                tile_shape=(2, 2), tol=1e-10)
    K, G = GramDriver(ds, _mesh(), VK, EK, **base).run_with_grad()
    K8, G8 = GramDriver(ds, _mesh(), VK, EK, tile=8,
                        **base).run_with_grad()
    np.testing.assert_allclose(K, K8, rtol=0, atol=1e-6)
    assert sorted(G) == sorted(G8)
    for key in G8:
        np.testing.assert_allclose(G[key], G8[key], rtol=1e-4, atol=1e-6)


def test_gram_tile_blocks_cover_all_pairs():
    ds = _dataset(11)
    from repro.data import gram_tile_blocks
    from repro.distributed.gram import _axis_structure
    blocks = list(gram_tile_blocks(ds, 3, 4))
    seen = set()
    for b in blocks:
        axes = _axis_structure(b.rows, b.cols)
        assert axes is not None      # every tile is a clean rectangle
        urows, ucols = axes
        assert len(b.rows) == len(urows) * len(ucols)
        for r, c in zip(b.rows, b.cols):
            seen.add((min(r, c), max(r, c)))
    n = len(ds)
    assert len(seen) == n * (n + 1) // 2


def test_pack_cache_rejects_non_multiple_tile():
    from repro.distributed.gram import GraphPackCache
    from repro.core.graph import batch_from_graphs
    gs = [g for g in make_drugbank_like_dataset(8, seed=1)
          if 6 <= g.n_nodes <= 24][:2]
    batch = batch_from_graphs(gs, pad_to=24)       # 24 % 16 != 0
    cache = GraphPackCache(tile=16)
    with pytest.raises(ValueError, match="multiple of"):
        cache.stacked(np.array([0, 1]), batch)


def test_gram_driver_sparse_matches_lowrank():
    ds = _dataset(6)
    drv_s = GramDriver(ds, _mesh(), VK, EK, method="pallas_sparse",
                       pairs_per_block=6)
    drv_l = GramDriver(ds, _mesh(), VK, EK, method="lowrank",
                       pairs_per_block=6)
    np.testing.assert_allclose(drv_s.run(), drv_l.run(), rtol=1e-4,
                               atol=1e-6)


def test_array_checkpoint_roundtrip_and_fallback(tmp_path):
    tree = {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
            "b": (np.ones(4), np.zeros(2))}
    save_array_checkpoint(str(tmp_path), 10, tree)
    save_array_checkpoint(str(tmp_path), 20, tree)
    restored, step = load_array_checkpoint(str(tmp_path), tree)
    assert step == 20
    np.testing.assert_allclose(restored["a"], tree["a"])
    # corrupt the latest; loader must fall back to step 10
    latest = sorted(p for p in os.listdir(tmp_path)
                    if p.endswith(".npz"))[-1]
    with open(os.path.join(tmp_path, latest), "r+b") as f:
        f.seek(40)
        f.write(b"\xde\xad\xbe\xef")
    restored, step = load_array_checkpoint(str(tmp_path), tree)
    assert step == 10


def test_gram_driver_kron_precond_matches_jacobi():
    """The full distributed kron path (cached factors: per-pair,
    per-axis gram-tile, and segmented retirement) must reproduce the
    Jacobi driver's Gram matrix — the preconditioner only changes the
    solve trajectory (DESIGN.md §9)."""
    import jax.numpy as jnp
    ds = _dataset(6)
    mesh = _mesh()
    base = dict(ds=ds, mesh=mesh, vertex_kernel=VK, edge_kernel=EK,
                method="pallas_sparse", tol=1e-8)
    ref = GramDriver(**base).run()
    for extra in (dict(),                                   # per-pair
                  dict(gram_tile=True, tile_shape=(2, 2)),  # per-axis
                  dict(gram_tile=True, tile_shape=(2, 2),
                       segment_size=4)):                    # retirement
        K = GramDriver(**base, precond="kron", **extra).run()
        np.testing.assert_allclose(K, ref, rtol=1e-5, atol=1e-7)
    # factors are cached once per (graph, pad): a second run through
    # the same driver instance reuses them
    d = GramDriver(**base, precond="kron")
    d.run()
    cache = d._pack_cache
    assert cache is not None and len(cache._factors) > 0
    # bf16 pack streaming through the driver: same Gram at bf16
    # resolution, and the cached pack buffers really are bfloat16
    db = GramDriver(**base, precond="kron", pack_dtype=jnp.bfloat16)
    Kb = db.run()
    np.testing.assert_allclose(Kb, ref, rtol=3e-2, atol=1e-3)
    entry = next(iter(db._pack_cache._packs.values()))
    assert entry["values_adj"].dtype == jnp.bfloat16


@pytest.mark.parametrize("mode", ["auto", "mxu"])
def test_gram_driver_kron_grad_matches_jacobi(mode):
    """run_with_grad under precond='kron' (adjoint reuses the cached
    factors via precond_factors/trust_pack_weights) matches Jacobi's
    gradient Gram blocks, in the contraction "auto" picks and in the
    MXU one."""
    ds = _dataset(5)
    mesh = _mesh()
    base = dict(ds=ds, mesh=mesh, vertex_kernel=VK, edge_kernel=EK,
                method="pallas_sparse", tol=1e-10, sparse_mode=mode)
    Kj, Gj = GramDriver(**base).run_with_grad()
    Kk, Gk = GramDriver(**base, precond="kron",
                        gram_tile=True,
                        tile_shape=(2, 2)).run_with_grad()
    np.testing.assert_allclose(Kk, Kj, rtol=1e-5, atol=1e-7)
    assert sorted(Gk) == sorted(Gj)
    for key in Gj:
        np.testing.assert_allclose(Gk[key], Gj[key], rtol=1e-3,
                                   atol=1e-6)


def test_gram_tile_vmem_bytes_tracks_pack_dtype():
    """The Gram-tile VMEM estimator must cost packs at their stored
    itemsize — bf16 packs halve the operand share, which is what lets
    larger tiles stay on the single-launch kernel."""
    import jax.numpy as jnp
    from repro.kernels.ops import row_panel_packs_for_batch
    from repro.kernels.xmv_block_sparse import gram_tile_vmem_bytes
    from repro.core import batch_from_graphs
    gs = [g for g in make_drugbank_like_dataset(10, seed=7)
          if 6 <= g.n_nodes <= 24][:4]
    g1 = batch_from_graphs(gs[:2], pad_to=24)
    g2 = batch_from_graphs(gs[2:], pad_to=24)
    pf1 = row_panel_packs_for_batch(g1, edge_kernel=EK)
    pf2 = row_panel_packs_for_batch(g2, edge_kernel=EK)
    pb1 = row_panel_packs_for_batch(g1, edge_kernel=EK,
                                    pack_dtype=jnp.bfloat16)
    pb2 = row_panel_packs_for_batch(g2, edge_kernel=EK,
                                    pack_dtype=jnp.bfloat16)
    for mxu in (False, True):
        f32 = gram_tile_vmem_bytes(pf1, pf2, mxu)
        bf16 = gram_tile_vmem_bytes(pb1, pb2, mxu)
        assert bf16 < f32
        # operand share halves exactly; the f32 share (the lane-
        # replicated P panel, t x the pair's n*m, plus diag/out strips)
        # stays
        assert f32 - bf16 == (f32 - 8 * (24 * 24 * 8 + 2 * 8 * 24)) // 2
