"""Row-panel block-sparse XMV: parity with the dense oracle across tile
sizes, ragged slot counts (including tile rows with ZERO real octiles),
the fused diagonal epilogue, single-launch jaxpr shape, and bf16 packs.

The kernels read and write P in tile-major order; the helpers below
convert so every comparison is against the node-major oracle."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.base_kernels import CompactPolynomial, KroneckerDelta, \
    SquareExponential
from repro.core.graph import batch_from_graphs
from repro.core.mgk import mgk_pairs_sparse
from repro.core.xmv import xmv_full
from repro.data import make_drugbank_like_dataset
from repro.kernels.ops import row_panel_packs_for_batch, \
    stack_row_panel_packs
from repro.kernels.xmv_block_sparse import from_tiles, \
    pack_graph_row_panels, to_tiles
from repro.kernels.xmv_block_sparse import xmv_gram_tile as _xmv_gram_tile
from repro.kernels.xmv_block_sparse import xmv_row_panel as _xmv_row_panel
from repro.kernels.xmv_block_sparse import \
    xmv_row_panel_batched as _xmv_row_panel_batched

VK = KroneckerDelta(0.5, n_labels=8)
EK = SquareExponential(1.0, rank=12)
TOL = dict(rtol=1e-5, atol=1e-5)


def _node_major(kernel):
    """Node-major view of a tile-major kernel: P/diag in, y out as
    [..., n, m]."""
    def call(p1, p2, P, ek, *, diag=None, **kw):
        t = p1.tile
        y = kernel(p1, p2, to_tiles(P, t), ek,
                   diag=None if diag is None else to_tiles(diag, t), **kw)
        return from_tiles(y)
    return call


xmv_row_panel = _node_major(_xmv_row_panel)
xmv_row_panel_batched = _node_major(_xmv_row_panel_batched)
xmv_gram_tile = _node_major(_xmv_gram_tile)


def _sparse_pair(rng, n, density=0.06, dead_band=None):
    """Random symmetric sparse graph; ``dead_band=(lo, hi)`` zeroes node
    rows/cols [lo, hi) so whole tile rows carry zero octiles."""
    a = (rng.random((n, n)) < density).astype(np.float32)
    a = np.triu(a, 1)
    a = a + a.T
    if dead_band is not None:
        lo, hi = dead_band
        a[lo:hi, :] = 0.0
        a[:, lo:hi] = 0.0
    e = rng.random((n, n)).astype(np.float32) * (a != 0)
    return a, e


def _oracle(a, e, ap, ep, P):
    return np.asarray(xmv_full(jnp.asarray(a), jnp.asarray(e),
                               jnp.asarray(ap), jnp.asarray(ep),
                               jnp.asarray(P), EK))


@pytest.mark.parametrize("tile", [8, 16, 32])
def test_row_panel_matches_oracle_all_tiles(rng, tile):
    """The per-pair kernel vs the full-materialization oracle for every
    supported octile edge (the acceptance parity sweep)."""
    n = 64
    a, e = _sparse_pair(rng, n)
    ap, ep = _sparse_pair(rng, n)
    P = rng.random((n, n)).astype(np.float32)
    ref = _oracle(a, e, ap, ep, P)
    p1 = pack_graph_row_panels(a, e, tile=tile)
    p2 = pack_graph_row_panels(ap, ep, tile=tile)
    y = xmv_row_panel(p1, p2, jnp.asarray(P), EK)
    np.testing.assert_allclose(np.asarray(y), ref, err_msg=f"t={tile}",
                               **TOL)


@pytest.mark.parametrize("tile", [8, 16])
def test_row_panel_ragged_and_empty_rows(rng, tile):
    """Rows with zero real octiles (count = 0) and strongly ragged slot
    counts must still be exact — the SMEM count predicates the in-kernel
    reduction."""
    n = 64
    # kill two whole tile-row bands on graph 1, one on graph 2
    a, e = _sparse_pair(rng, n, density=0.15,
                        dead_band=(tile, 2 * tile))
    a[3 * tile:4 * tile, :] = 0.0
    a[:, 3 * tile:4 * tile] = 0.0
    e = e * (a != 0)
    ap, ep = _sparse_pair(rng, n, density=0.03, dead_band=(0, tile))
    P = rng.random((n, n)).astype(np.float32)
    ref = _oracle(a, e, ap, ep, P)
    p1 = pack_graph_row_panels(a, e, tile=tile)
    p2 = pack_graph_row_panels(ap, ep, tile=tile)
    assert int(np.asarray(p1.count).min()) == 0     # truly empty rows
    y = xmv_row_panel(p1, p2, jnp.asarray(P), EK)
    np.testing.assert_allclose(np.asarray(y), ref, **TOL)


def test_row_panel_elementwise_only_kernel(rng):
    """Edge kernels without a feature expansion run the same kernel:
    the sparse body regenerates kappa elementwise for any edge kernel."""
    ck = CompactPolynomial(1.0)
    assert ck.feature_rank() is None
    n = 40
    a, e = _sparse_pair(rng, n, density=0.1)
    ap, ep = _sparse_pair(rng, n, density=0.1)
    P = rng.random((n, n)).astype(np.float32)
    p1 = pack_graph_row_panels(a, e)
    p2 = pack_graph_row_panels(ap, ep)
    ref = np.asarray(xmv_full(jnp.asarray(a), jnp.asarray(e),
                              jnp.asarray(ap), jnp.asarray(ep),
                              jnp.asarray(P), ck))
    y = xmv_row_panel(p1, p2, jnp.asarray(P), ck)
    np.testing.assert_allclose(np.asarray(y), ref, **TOL)


@pytest.fixture(scope="module")
def masked_batch():
    gs = make_drugbank_like_dataset(16, seed=11)
    gs = [g for g in gs if 6 <= g.n_nodes <= 48][:8]
    assert len(gs) == 8
    g1 = batch_from_graphs(gs[:4], pad_to=48)
    g2 = batch_from_graphs(gs[4:], pad_to=48)
    return g1, g2


def _random_p(g1, g2, seed=0):
    rng = np.random.default_rng(seed)
    B, n = g1.adjacency.shape[:2]
    m = g2.adjacency.shape[1]
    return jnp.asarray(rng.random((B, n, m)).astype(np.float32))


def test_batched_row_panel_matches_oracle(masked_batch):
    g1, g2 = masked_batch
    P = _random_p(g1, g2)
    args = (g1.adjacency, g1.edge_labels, g2.adjacency, g2.edge_labels, P)
    ref = np.asarray(jax.vmap(
        lambda a, e, ap, ep, p: xmv_full(a, e, ap, ep, p, EK))(*args))
    r1 = row_panel_packs_for_batch(g1)
    r2 = row_panel_packs_for_batch(g2)
    y = xmv_row_panel_batched(r1, r2, P, EK)
    np.testing.assert_allclose(np.asarray(y), ref, **TOL)


def test_batched_row_panel_fused_epilogue(masked_batch):
    g1, g2 = masked_batch
    P = _random_p(g1, g2)
    rng = np.random.default_rng(1)
    diag = jnp.asarray(rng.random(P.shape).astype(np.float32) + 1.0)
    r1 = row_panel_packs_for_batch(g1)
    r2 = row_panel_packs_for_batch(g2)
    y = xmv_row_panel_batched(r1, r2, P, EK)
    ref = np.asarray(diag) * np.asarray(P) - np.asarray(y)
    fused = xmv_row_panel_batched(r1, r2, P, EK, diag=diag)
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


def test_row_panel_is_single_launch(masked_batch):
    """The row-panel bucket matvec must still be exactly ONE pallas_call
    per matvec — the in-kernel slot reduction must not re-introduce
    per-slot (or per-pair) launches."""
    g1, g2 = masked_batch
    P = _random_p(g1, g2)
    r1 = row_panel_packs_for_batch(g1)
    r2 = row_panel_packs_for_batch(g2)
    n_calls = _count_primitive(
        jax.make_jaxpr(
            lambda P: _xmv_row_panel_batched(r1, r2, P, EK)
        )(to_tiles(P, r1.tile)).jaxpr, "pallas_call")
    assert n_calls == 1, f"traced {n_calls} pallas_calls"


def test_stack_row_panel_packs_rejects_mixed(rng):
    """Packs of one stack share their slot count: packs mixing k_max
    (not padded to the bucket's) are refused, not broadcast."""
    a, e = _sparse_pair(rng, 16, density=0.2)
    loose = pack_graph_row_panels(a, e)
    wide = pack_graph_row_panels(a, e, k_max=loose.k_max + 1)
    with pytest.raises(ValueError):
        stack_row_panel_packs([loose, wide])


# -- bf16 pack streaming (DESIGN.md §9.4) ----------------------------------
#
# pack_dtype=jnp.bfloat16 halves the HBM bytes every matvec streams;
# the kernels upcast operands in VMEM and accumulate in f32, so the
# only precision cost is ONE rounding of the stored values — parity
# against the f32-pack result holds at bf16 input resolution
# (rel eps 2^-8), never compounded.

BF16_TOL = dict(rtol=3e-2, atol=1e-3)


@pytest.mark.parametrize("kernel,tile", [("row-panel", 8),
                                         ("gram-tile", 32)])
def test_bf16_pack_oracle_parity(rng, kernel, tile):
    """bf16-stored packs vs the f32 dense oracle: the per-pair kernel at
    t = 8, and the Gram-tile kernel at t = 32, the edge the Gram-tile
    step picks on 32- and 96-node buckets."""
    n = 32
    a, e = _sparse_pair(rng, n, density=0.15)
    ap, ep = _sparse_pair(rng, n, density=0.15)
    P = rng.random((n, n)).astype(np.float32)
    ref = _oracle(a, e, ap, ep, P)
    p1 = pack_graph_row_panels(a, e, tile=tile, pack_dtype=jnp.bfloat16)
    p2 = pack_graph_row_panels(ap, ep, tile=tile, pack_dtype=jnp.bfloat16)
    assert p1.values_adj.dtype == jnp.bfloat16
    assert p1.values_lab.dtype == jnp.bfloat16
    if kernel == "row-panel":
        y = xmv_row_panel(p1, p2, jnp.asarray(P), EK)
    else:
        y = xmv_gram_tile(stack_row_panel_packs([p1]),
                          stack_row_panel_packs([p2]),
                          jnp.asarray(P)[None, None], EK)[0, 0]
    assert y.dtype == jnp.float32    # f32 accumulators, f32 output
    np.testing.assert_allclose(np.asarray(y), ref, **BF16_TOL)


def test_bf16_batched_and_solve_parity(masked_batch):
    """Whole-bucket bf16 packs: batched kernel vs f32 packs, and the
    end-to-end MGK solve at appropriately loosened tolerance."""
    g1, g2 = masked_batch
    from repro.kernels.xmv_block_sparse import resolve_pack_dtype
    assert resolve_pack_dtype("bfloat16") == resolve_pack_dtype(
        jnp.bfloat16)
    p1f = row_panel_packs_for_batch(g1)
    p2f = row_panel_packs_for_batch(g2)
    p1b = row_panel_packs_for_batch(g1, pack_dtype=jnp.bfloat16)
    p2b = row_panel_packs_for_batch(g2, pack_dtype=jnp.bfloat16)
    # halved value-buffer footprint is the point: assert it
    assert p1b.values_adj.nbytes * 2 == p1f.values_adj.nbytes
    assert p1b.values_lab.nbytes * 2 == p1f.values_lab.nbytes
    P = _random_p(g1, g2)
    yf = xmv_row_panel_batched(p1f, p2f, P, EK)
    yb = xmv_row_panel_batched(p1b, p2b, P, EK)
    np.testing.assert_allclose(np.asarray(yb), np.asarray(yf), **BF16_TOL)
    rf = mgk_pairs_sparse(g1, g2, p1f, p2f, VK, EK, tol=1e-8)
    rb = mgk_pairs_sparse(g1, g2, p1b, p2b, VK, EK, tol=1e-8)
    np.testing.assert_allclose(np.asarray(rb.values),
                               np.asarray(rf.values), **BF16_TOL)
    # and with the kron preconditioner riding along
    rk = mgk_pairs_sparse(g1, g2, p1b, p2b, VK, EK, tol=1e-8,
                          precond="kron")
    np.testing.assert_allclose(np.asarray(rk.values),
                               np.asarray(rf.values), **BF16_TOL)
