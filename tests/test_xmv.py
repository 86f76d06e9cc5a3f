"""XMV backends: all must agree with the full-materialization oracle
across shapes / dtypes / kernels (the per-kernel allclose requirement)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from _hypothesis_compat import given, settings, st

from repro.core.base_kernels import CompactPolynomial, Constant, \
    SquareExponential
from repro.core.xmv import xmv_elementwise, xmv_full, xmv_lowrank
from repro.kernels.ref import xmv_ref
from repro.kernels.xmv_block_sparse import from_tiles, to_tiles
from repro.kernels.xmv_dense import DENSE_TILE
from repro.kernels.xmv_dense import xmv_dense as _xmv_dense
from repro.kernels.ops import stack_row_panel_packs
from repro.kernels.xmv_block_sparse import pack_graph_row_panels, \
    xmv_row_panel_batched


def xmv_dense(A, E, Ap, Ep, P, ek):
    """Node-major view of the tile-major dense kernel."""
    return from_tiles(_xmv_dense(A, E, Ap, Ep,
                                 to_tiles(jnp.asarray(P), DENSE_TILE), ek))


def xmv_sparse(A, E, Ap, Ep, P, ek, tile=8):
    """Node-major view of the row-panel kernel on one pair, run as a
    bucket of one (``xmv_row_panel_batched``)."""
    p1 = stack_row_panel_packs([pack_graph_row_panels(A, E, tile=tile)])
    p2 = stack_row_panel_packs([pack_graph_row_panels(Ap, Ep, tile=tile)])
    y = xmv_row_panel_batched(p1, p2, to_tiles(jnp.asarray(P), tile)[None],
                              ek)
    return from_tiles(y[0])


EDGE_KERNELS = [Constant(1.0), SquareExponential(0.8, rank=12),
                CompactPolynomial(1.0)]


def _pair(rng, n, m, density=1.0, dtype=np.float32):
    def mat(s):
        a = rng.random((s, s)).astype(dtype)
        if density < 1.0:
            a *= rng.random((s, s)) < density
        a = np.triu(a, 1)
        a = a + a.T
        e = rng.random((s, s)).astype(dtype) * (a != 0)
        return a, e
    A, E = mat(n)
    Ap, Ep = mat(m)
    P = rng.random((n, m)).astype(dtype)
    return A, E, Ap, Ep, P


@pytest.mark.parametrize("ek", EDGE_KERNELS, ids=lambda k: type(k).__name__)
@pytest.mark.parametrize("n,m", [(8, 8), (16, 24), (32, 16)])
def test_elementwise_matches_full(ek, n, m, rng):
    A, E, Ap, Ep, P = _pair(rng, n, m)
    y_full = xmv_full(A, E, Ap, Ep, P, ek)
    y_elem = xmv_elementwise(A, E, Ap, Ep, P, ek)
    np.testing.assert_allclose(y_elem, y_full, rtol=2e-5, atol=1e-5)


@pytest.mark.parametrize("ek", EDGE_KERNELS[:2],
                         ids=lambda k: type(k).__name__)
def test_lowrank_matches_full(ek, rng):
    A, E, Ap, Ep, P = _pair(rng, 16, 24)
    y_full = xmv_full(A, E, Ap, Ep, P, ek)
    y_lr = xmv_lowrank(A, E, Ap, Ep, P, ek)
    np.testing.assert_allclose(y_lr, y_full, rtol=2e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,m", [(8, 8), (16, 16), (24, 40), (64, 32),
                                 (128, 128)])
def test_pallas_dense_sweep(n, m, dtype, rng):
    ek = SquareExponential(1.0, rank=10)
    A, E, Ap, Ep, P = _pair(rng, n, m)
    conv = lambda x: jnp.asarray(x, dtype)  # noqa: E731
    y = xmv_dense(conv(A), conv(E), conv(Ap), conv(Ep), conv(P), ek)
    y_ref = xmv_ref(jnp.asarray(A), jnp.asarray(E), jnp.asarray(Ap),
                    jnp.asarray(Ep), jnp.asarray(P), ek)
    tol = 2e-5 if dtype == np.float32 else 0.05
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref), rtol=tol, atol=tol)


@pytest.mark.parametrize("n,m,density", [(16, 16, 0.1), (32, 48, 0.05),
                                         (64, 64, 0.15), (40, 24, 0.3)])
def test_pallas_block_sparse_sweep(n, m, density, rng):
    ek = SquareExponential(1.0, rank=10)
    A, E, Ap, Ep, P = _pair(rng, n, m, density=density)
    y = xmv_sparse(A, E, Ap, Ep, P, ek)
    y_ref = xmv_ref(jnp.asarray(A), jnp.asarray(E), jnp.asarray(Ap),
                    jnp.asarray(Ep), jnp.asarray(P), ek)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-5, atol=1e-5)


def test_pallas_block_sparse_empty_graph(rng):
    ek = Constant(1.0)
    A = np.zeros((16, 16), np.float32)
    E = np.zeros_like(A)
    Ap, Ep, P = rng.random((24, 24)).astype(np.float32), None, None
    Ap = np.triu(Ap, 1) + np.triu(Ap, 1).T
    Ep = Ap.copy()
    P = rng.random((16, 24)).astype(np.float32)
    y = xmv_sparse(A, E, Ap, Ep, P, ek)
    assert np.allclose(np.asarray(y), 0.0)


@settings(max_examples=15, deadline=None)
@given(n=st.sampled_from([8, 16, 24]), m=st.sampled_from([8, 16, 32]),
       seed=st.integers(0, 100))
def test_pallas_dense_property(n, m, seed):
    rng = np.random.default_rng(seed)
    ek = Constant(1.0)
    A, E, Ap, Ep, P = _pair(rng, n, m)
    y = xmv_dense(A, E, Ap, Ep, P, ek)
    y_ref = xmv_ref(jnp.asarray(A), jnp.asarray(E), jnp.asarray(Ap),
                    jnp.asarray(Ep), jnp.asarray(P), ek)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-5, atol=1e-5)
