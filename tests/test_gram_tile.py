"""Gram-tile hot path (DESIGN.md §8): oracle-parity for the cross-pair
``xmv_gram_tile`` kernel (per-axis packs, (Bi, nt, Bj) grid) against
``mgk_direct``/``xmv_gram_full`` AND the per-pair row-panel kernel,
covering ragged Bi != Bj tiles, ragged n != m pads, zero-octile rows,
every octile edge and edge kernel (static and traced hyperparameters),
the fused epilogue, and the single-launch jaxpr;
plus convergence-segmented PCG pinned iterate-for-iterate against masked
lockstep with strictly fewer pair-matvec evaluations."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.base_kernels import CompactPolynomial, Constant, \
    KroneckerDelta, ParamDerivative, SquareExponential, pack_theta
from repro.core.graph import batch_from_graphs
from repro.core.mgk import mgk_pairs_sparse, mgk_pairs_sparse_segmented
from repro.core.pcg import pcg_solve, pcg_solve_segmented
from repro.core.xmv import xmv_gram_full
from repro.data import make_drugbank_like_dataset
from repro.kernels.ops import row_panel_packs_for_batch, \
    stack_row_panel_packs
from repro.kernels.xmv_block_sparse import from_tiles, \
    pack_graph_row_panels, to_tiles
from repro.kernels.xmv_block_sparse import xmv_gram_tile as _xmv_gram_tile
from repro.kernels.xmv_block_sparse import \
    xmv_row_panel_batched as _xmv_row_panel_batched

VK = KroneckerDelta(0.5, n_labels=8)
EK = SquareExponential(1.0, rank=12)
TOL = dict(rtol=1e-5, atol=1e-5)


def _node_major(kernel):
    """Node-major view of a tile-major kernel (P/diag in, y out as
    [..., n, m]) so comparisons run against the node-major oracles."""
    def call(p1, p2, P, ek, *, diag=None, **kw):
        t = p1.tile
        y = kernel(p1, p2, to_tiles(P, t), ek,
                   diag=None if diag is None else to_tiles(diag, t), **kw)
        return from_tiles(y)
    return call


xmv_gram_tile = _node_major(_xmv_gram_tile)
xmv_row_panel_batched = _node_major(_xmv_row_panel_batched)


def _sparse_pair(rng, n, density=0.08, dead_band=None):
    a = (rng.random((n, n)) < density).astype(np.float32)
    a = np.triu(a, 1)
    a = a + a.T
    if dead_band is not None:
        lo, hi = dead_band
        a[lo:hi, :] = 0.0
        a[:, lo:hi] = 0.0
    e = rng.random((n, n)).astype(np.float32) * (a != 0)
    return a, e


def _axis_packs(graphs, tile=8):
    """Stack per-graph row-panel packs at the axis-shared k_max."""
    loose = [pack_graph_row_panels(a, e, tile=tile) for a, e in graphs]
    k_max = max(p.k_max for p in loose)
    return stack_row_panel_packs(
        [pack_graph_row_panels(a, e, tile=tile, k_max=k_max)
         for a, e in graphs])


def _stack(graphs, which):
    return jnp.asarray(np.stack([g[which] for g in graphs]))


@pytest.mark.parametrize("Bi,Bj,n,m", [(3, 5, 32, 48), (4, 2, 40, 40)])
def test_gram_tile_matches_oracle_ragged(rng, Bi, Bj, n, m):
    """Ragged Bi != Bj and n != m cross tiles vs the doubly-vmapped
    full-materialization oracle; graph 0 carries zero-octile tile-row
    bands (count = 0 rows)."""
    rows = [_sparse_pair(rng, n, dead_band=(8, 16) if i == 0 else None)
            for i in range(Bi)]
    cols = [_sparse_pair(rng, m, dead_band=(0, 8) if j == 1 else None)
            for j in range(Bj)]
    P = jnp.asarray(rng.random((Bi, Bj, n, m)).astype(np.float32))
    ref = np.asarray(xmv_gram_full(_stack(rows, 0), _stack(rows, 1),
                                   _stack(cols, 0), _stack(cols, 1),
                                   P, EK))
    p1 = _axis_packs(rows)
    p2 = _axis_packs(cols)
    assert int(np.asarray(p1.count).min()) == 0  # truly empty
    y = xmv_gram_tile(p1, p2, P, EK)
    np.testing.assert_allclose(np.asarray(y), ref, **TOL)


GRAM_TILE_EDGE_KERNELS = {
    "constant": Constant(1.0),
    "square-exponential": EK,
    "compact-polynomial": CompactPolynomial(0.5),
}


@pytest.mark.parametrize("ek_name", list(GRAM_TILE_EDGE_KERNELS))
@pytest.mark.parametrize("tile", [8, 16, 32])
def test_gram_tile_matches_oracle_at_every_edge(rng, tile, ek_name):
    """The Gram-tile kernel vs ``xmv_gram_full`` at each octile edge the
    step can pick, for an expandable, a constant and a non-expandable
    edge kernel (the body regenerates kappa elementwise for all)."""
    ek = GRAM_TILE_EDGE_KERNELS[ek_name]
    Bi, Bj, n, m = 2, 3, 64, 32
    rows = [_sparse_pair(rng, n) for _ in range(Bi)]
    cols = [_sparse_pair(rng, m) for _ in range(Bj)]
    P = jnp.asarray(rng.random((Bi, Bj, n, m)).astype(np.float32))
    ref = np.asarray(xmv_gram_full(_stack(rows, 0), _stack(rows, 1),
                                   _stack(cols, 0), _stack(cols, 1),
                                   P, ek))
    y = xmv_gram_tile(_axis_packs(rows, tile), _axis_packs(cols, tile),
                      P, ek)
    np.testing.assert_allclose(np.asarray(y), ref, **TOL)


@pytest.mark.parametrize("tile", [8, 16, 32])
def test_gram_tile_traced_theta_matches_oracle(rng, tile):
    """The adjoint's call: ``ParamDerivative`` as the edge kernel and
    its hyperparameters as a traced ``theta`` vector (away from the
    kernel's static value) vs the oracle at the same theta."""
    dk = ParamDerivative(EK, "alpha")
    theta = {"alpha": 0.6}
    Bi, Bj, n = 2, 2, 64
    rows = [_sparse_pair(rng, n) for _ in range(Bi)]
    cols = [_sparse_pair(rng, n) for _ in range(Bj)]
    P = jnp.asarray(rng.random((Bi, Bj, n, n)).astype(np.float32))
    ref = np.asarray(xmv_gram_full(_stack(rows, 0), _stack(rows, 1),
                                   _stack(cols, 0), _stack(cols, 1),
                                   P, dk, theta=theta))
    p1, p2 = _axis_packs(rows, tile), _axis_packs(cols, tile)
    y = jax.jit(lambda tv: xmv_gram_tile(p1, p2, P, dk, theta=tv))(
        pack_theta(dk, theta))
    np.testing.assert_allclose(np.asarray(y), ref, **TOL)


def test_gram_tile_matches_per_pair_kernel(rng):
    """Per-axis Gram-tile execution vs the per-pair row-panel kernel on
    the stacked pair expansion — same values from Bi + Bj packs instead
    of Bi*Bj."""
    Bi, Bj, n = 3, 4, 32
    rows = [_sparse_pair(rng, n) for _ in range(Bi)]
    cols = [_sparse_pair(rng, n) for _ in range(Bj)]
    P = jnp.asarray(rng.random((Bi, Bj, n, n)).astype(np.float32))
    flat_rows = [rows[b // Bj] for b in range(Bi * Bj)]
    flat_cols = [cols[b % Bj] for b in range(Bi * Bj)]
    y = xmv_gram_tile(_axis_packs(rows), _axis_packs(cols), P, EK)
    yp = xmv_row_panel_batched(_axis_packs(flat_rows),
                               _axis_packs(flat_cols),
                               P.reshape(Bi * Bj, n, n), EK)
    np.testing.assert_allclose(np.asarray(y).reshape(Bi * Bj, n, n),
                               np.asarray(yp), **TOL)


def test_gram_tile_fused_epilogue(rng):
    Bi, Bj, n = 2, 3, 32
    rows = [_sparse_pair(rng, n) for _ in range(Bi)]
    cols = [_sparse_pair(rng, n) for _ in range(Bj)]
    P = jnp.asarray(rng.random((Bi, Bj, n, n)).astype(np.float32))
    diag = jnp.asarray(rng.random(P.shape).astype(np.float32) + 1.0)
    p1, p2 = _axis_packs(rows), _axis_packs(cols)
    y = xmv_gram_tile(p1, p2, P, EK)
    fused = xmv_gram_tile(p1, p2, P, EK, diag=diag)
    ref = np.asarray(diag) * np.asarray(P) - np.asarray(y)
    np.testing.assert_allclose(np.asarray(fused), ref, **TOL)


def _count_primitive(jaxpr, name):
    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            count += 1
        for v in eqn.params.values():
            if isinstance(v, jax.extend.core.ClosedJaxpr):
                count += _count_primitive(v.jaxpr, name)
            elif isinstance(v, jax.extend.core.Jaxpr):
                count += _count_primitive(v, name)
    return count


def test_gram_tile_is_single_launch(rng):
    """The whole Bi x Bj cross-product matvec must be exactly ONE
    pallas_call — the pair axes ride the grid, not a launch loop."""
    Bi, Bj, n = 3, 4, 32
    rows = [_sparse_pair(rng, n) for _ in range(Bi)]
    cols = [_sparse_pair(rng, n) for _ in range(Bj)]
    P = jnp.asarray(rng.random((Bi, Bj, n, n)).astype(np.float32))
    p1, p2 = _axis_packs(rows), _axis_packs(cols)
    n_calls = _count_primitive(
        jax.make_jaxpr(
            lambda P: _xmv_gram_tile(p1, p2, P, EK)
        )(to_tiles(P, p1.tile)).jaxpr, "pallas_call")
    assert n_calls == 1, f"traced {n_calls} pallas_calls"


@pytest.fixture(scope="module")
def tile_batches():
    """(row batch [Bi], col batch [Bj], flattened pair batches) of real
    drugbank-like graphs."""
    gs = [g for g in make_drugbank_like_dataset(24, seed=11)
          if 6 <= g.n_nodes <= 40]
    Bi, Bj = 3, 4
    g1u = batch_from_graphs(gs[:Bi], pad_to=40)
    g2u = batch_from_graphs(gs[Bi:Bi + Bj], pad_to=40)
    rep = lambda x: jnp.repeat(x, Bj, axis=0)                   # noqa
    til = lambda x: jnp.tile(x, (Bi,) + (1,) * (x.ndim - 1))    # noqa
    return (Bi, Bj), g1u, g2u, jax.tree.map(rep, g1u), \
        jax.tree.map(til, g2u)


def test_mgk_gram_tile_matches_direct_and_per_pair(tile_batches):
    """mgk_pairs_sparse(gram_tile=...) vs the LAPACK oracle (mgk_direct)
    and the per-pair sparse solve."""
    from repro.core.graph import Graph
    from repro.core.reference import mgk_direct
    (Bi, Bj), g1u, g2u, g1f, g2f = tile_batches

    def to_graph(gb, b):
        k = int(gb.n_nodes[b])
        return Graph(
            adjacency=np.asarray(gb.adjacency[b])[:k, :k],
            vertex_labels=np.asarray(gb.vertex_labels[b])[:k],
            edge_labels=np.asarray(gb.edge_labels[b])[:k, :k],
            start_prob=np.asarray(gb.start_prob[b])[:k],
            stop_prob=np.asarray(gb.stop_prob[b])[:k])

    direct = np.array([
        mgk_direct(to_graph(g1u, b // Bj), to_graph(g2u, b % Bj), VK, EK)
        for b in range(Bi * Bj)])
    a1 = row_panel_packs_for_batch(g1u)
    a2 = row_panel_packs_for_batch(g2u)
    res = mgk_pairs_sparse(g1f, g2f, a1, a2, VK, EK, tol=1e-10,
                           gram_tile=(Bi, Bj))
    np.testing.assert_allclose(np.asarray(res.values), direct, rtol=1e-4)
    p1 = row_panel_packs_for_batch(g1f)
    p2 = row_panel_packs_for_batch(g2f)
    ref = mgk_pairs_sparse(g1f, g2f, p1, p2, VK, EK, tol=1e-10)
    np.testing.assert_allclose(np.asarray(res.values),
                               np.asarray(ref.values), rtol=1e-5)
    assert np.array_equal(np.asarray(res.iterations),
                          np.asarray(ref.iterations))


def test_gram_tile_adjoint_grads_match_per_pair(tile_batches):
    """The adjoint path dispatches to the Gram-tile kernel unchanged:
    per-pair hyperparameter gradients from per-axis packs must match the
    per-pair row-panel gradients."""
    from repro.core.adjoint import kernel_theta, mgk_value_fn
    (Bi, Bj), g1u, g2u, g1f, g2f = tile_batches
    theta = kernel_theta(VK, EK)
    a1 = row_panel_packs_for_batch(g1u)
    a2 = row_panel_packs_for_batch(g2u)
    p1 = row_panel_packs_for_batch(g1f)
    p2 = row_panel_packs_for_batch(g2f)
    fn_t = mgk_value_fn(g1f, g2f, VK, EK, method="sparse", packs1=a1,
                        packs2=a2, gram_tile=(Bi, Bj))
    fn_p = mgk_value_fn(g1f, g2f, VK, EK, method="sparse", packs1=p1,
                        packs2=p2)
    vt, gt = fn_t.value_and_pair_grads(theta)
    vp, gp = fn_p.value_and_pair_grads(theta)
    np.testing.assert_allclose(np.asarray(vt), np.asarray(vp), rtol=1e-5)
    for group in gt:
        for name in gt[group]:
            np.testing.assert_allclose(
                np.asarray(gt[group][name]), np.asarray(gp[group][name]),
                rtol=2e-3, atol=2e-6, err_msg=f"{group}.{name}")


# -- convergence-segmented PCG ----------------------------------------------

def _mixed_spd(rng, B, N):
    """SPD batch with deliberately mixed conditioning -> mixed
    convergence (the pair-retirement scenario)."""
    a = rng.random((B, N, N)).astype(np.float32)
    spd = np.einsum("bij,bkj->bik", a, a) + \
        N * np.eye(N, dtype=np.float32)[None]
    for i in range(B // 2):
        spd[i] = np.eye(N, dtype=np.float32) * (i + 2) \
            + 0.01 * spd[i] / N
    return spd


@pytest.mark.parametrize("variant", ["classic", "pipelined"])
@pytest.mark.parametrize("pad_multiple", [1, 4])
def test_segmented_matches_lockstep_iterate_for_iterate(rng, variant,
                                                        pad_multiple):
    B, N = 6, 32
    spd = _mixed_spd(rng, B, N)
    b = rng.random((B, N)).astype(np.float32)
    diag = jnp.asarray(np.einsum("bii->bi", spd))
    spd_j = jnp.asarray(spd)
    mv = lambda p: jnp.einsum("bij,bj->bi", spd_j, p)       # noqa

    def select(lanes):
        sub = spd_j[jnp.asarray(lanes)]
        return lambda p: jnp.einsum("bij,bj->bi", sub, p)

    lock = pcg_solve(mv, jnp.asarray(b), diag, tol=1e-10, max_iter=500,
                     variant=variant)
    seg = pcg_solve_segmented(mv, jnp.asarray(b), diag, tol=1e-10,
                              max_iter=500, segment_size=8,
                              variant=variant, select=select,
                              pad_multiple=pad_multiple)
    # identical per-pair trajectories: same iteration counts, same
    # solutions, same final residuals
    assert np.array_equal(np.asarray(lock.iterations),
                          np.asarray(seg.iterations))
    np.testing.assert_allclose(np.asarray(seg.x), np.asarray(lock.x),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(seg.residual),
                               np.asarray(lock.residual),
                               rtol=1e-5, atol=1e-12)
    assert bool(np.asarray(seg.converged).all())
    # ... at strictly fewer pair-matvec evaluations (mixed convergence)
    assert int(np.asarray(lock.iterations).max()) \
        > int(np.asarray(lock.iterations).min())
    assert int(seg.matvec_pairs) < int(lock.matvec_pairs)


def test_segmented_without_select_matches_lockstep(rng):
    """No ``select`` -> no compaction: results still identical, work
    identical to lockstep (the honesty contract of matvec_pairs)."""
    B, N = 4, 24
    spd = _mixed_spd(rng, B, N)
    b = rng.random((B, N)).astype(np.float32)
    diag = jnp.asarray(np.einsum("bii->bi", spd))
    spd_j = jnp.asarray(spd)
    mv = lambda p: jnp.einsum("bij,bj->bi", spd_j, p)       # noqa
    lock = pcg_solve(mv, jnp.asarray(b), diag, tol=1e-10, max_iter=500)
    seg = pcg_solve_segmented(mv, jnp.asarray(b), diag, tol=1e-10,
                              max_iter=500, segment_size=8)
    assert np.array_equal(np.asarray(lock.iterations),
                          np.asarray(seg.iterations))
    np.testing.assert_allclose(np.asarray(seg.x), np.asarray(lock.x),
                               rtol=1e-6, atol=1e-7)
    assert int(seg.matvec_pairs) == int(lock.matvec_pairs)


def test_mgk_segmented_sparse_gram_tile(tile_batches):
    """Segmented solve over a Gram tile: identical values/iterations to
    lockstep, strictly fewer pair-matvec evaluations; per-pair packs
    path included."""
    (Bi, Bj), g1u, g2u, g1f, g2f = tile_batches
    a1 = row_panel_packs_for_batch(g1u)
    a2 = row_panel_packs_for_batch(g2u)
    lock = mgk_pairs_sparse(g1f, g2f, a1, a2, VK, EK, tol=1e-10,
                            gram_tile=(Bi, Bj))
    its = np.asarray(lock.iterations)
    assert its.max() > its.min()     # a genuinely mixed bucket
    seg = mgk_pairs_sparse_segmented(g1f, g2f, a1, a2, VK, EK,
                                     tol=1e-10, segment_size=4,
                                     gram_tile=(Bi, Bj))
    np.testing.assert_allclose(np.asarray(seg.values),
                               np.asarray(lock.values), rtol=1e-6)
    assert np.array_equal(its, np.asarray(seg.iterations))
    assert int(seg.matvec_pairs) < int(lock.matvec_pairs)
    # per-pair packs, same contract
    p1 = row_panel_packs_for_batch(g1f)
    p2 = row_panel_packs_for_batch(g2f)
    seg_p = mgk_pairs_sparse_segmented(g1f, g2f, p1, p2, VK, EK,
                                       tol=1e-10, segment_size=4)
    np.testing.assert_allclose(np.asarray(seg_p.values),
                               np.asarray(lock.values), rtol=1e-6)
    assert int(seg_p.matvec_pairs) < int(lock.matvec_pairs)
