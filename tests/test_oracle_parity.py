"""Property-based oracle parity: hypothesis-generated random labeled
graphs through EVERY mgk_adaptive backend — the four dispatch-table
cells plus the jnp reference paths and the adaptive entry itself — all
compared against the ``core/reference.mgk_direct`` dense LAPACK oracle
in ONE parameterized test. This subsumes the per-kernel parity checks
scattered through test_mgk/test_adaptive/test_row_panel (kept as fast
regression pins); new backends only need a row here.

Runs under the seeded hypothesis profile from conftest.py ("ci" =
derandomized) or the deterministic _hypothesis_compat grid when
hypothesis is not installed.
"""
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import (CompactPolynomial, KroneckerDelta,
                        SquareExponential, batch_from_graphs, mgk_pairs)
from repro.core.mgk import mgk_adaptive, mgk_pairs_sparse
from repro.core.reference import mgk_direct
from repro.data import make_synthetic_dataset
from repro.kernels.ops import row_panel_packs_for_batch

VK = KroneckerDelta(0.5, n_labels=8)
SE = SquareExponential(1.0, rank=12)
CP = CompactPolynomial(1.0)

# every backend the adaptive table can dispatch to, plus the adaptive
# entry itself; (mode, edge_kernel, needs_packs)
BACKENDS = [
    ("full", SE), ("elementwise", SE), ("lowrank", SE),
    ("pallas", SE), ("pallas", CP),
    ("sparse_vpu", CP), ("sparse_vpu", SE), ("sparse_gram_tile", SE),
    ("adaptive", SE), ("adaptive", CP),
]


def _graph_pair(gtype: str, n: int, seed: int, q: float):
    gs = make_synthetic_dataset(gtype, n_graphs=2, n_nodes=n, seed=seed,
                                stop_prob=q)
    return gs[0], gs[1]


def _run_backend(mode, ek, g1b, g2b):
    if mode == "adaptive":
        return mgk_adaptive(g1b, g2b, VK, ek, tol=1e-12)
    if mode == "sparse_gram_tile":
        # a 1 x 1 Gram tile at the largest edge the pads allow, as the
        # Gram driver picks it
        n = g1b.adjacency.shape[1]
        t = next(t for t in (32, 16, 8) if n % t == 0)
        p1 = row_panel_packs_for_batch(g1b, tile=t)
        p2 = row_panel_packs_for_batch(g2b, tile=t)
        return mgk_pairs_sparse(g1b, g2b, p1, p2, VK, ek, tol=1e-12,
                                gram_tile=(1, 1))
    if mode.startswith("sparse"):
        p1 = row_panel_packs_for_batch(g1b)
        p2 = row_panel_packs_for_batch(g2b)
        return mgk_pairs_sparse(g1b, g2b, p1, p2, VK, ek, tol=1e-12)
    return mgk_pairs(g1b, g2b, VK, ek, method=mode, tol=1e-12)


@pytest.mark.parametrize("mode,ek", BACKENDS,
                         ids=[f"{m}-{type(k).__name__}"
                              for m, k in BACKENDS])
@settings(max_examples=12, deadline=None)
@given(gtype=st.sampled_from(["nws", "ba"]),
       n=st.integers(8, 18),
       seed=st.integers(0, 4),
       q=st.floats(0.05, 0.4))
def test_backend_matches_direct_oracle(mode, ek, gtype, n, seed, q):
    g1, g2 = _graph_pair(gtype, n, seed, q)
    g1b = batch_from_graphs([g1])
    g2b = batch_from_graphs([g2])
    res = _run_backend(mode, ek, g1b, g2b)
    ref = mgk_direct(g1, g2, VK, ek)
    # rtol covers f32 accumulation + the SE expansion's rank-12
    # truncation on the lowrank path
    np.testing.assert_allclose(float(res.values[0]), ref, rtol=2e-3)
    assert bool(res.converged.all())


@settings(max_examples=8, deadline=None)
@given(n1=st.integers(8, 14), n2=st.integers(8, 14),
       seed=st.integers(0, 3))
def test_rectangular_pairs_match_oracle(n1, n2, seed):
    """Cross-bucket pairs (n != m, different pads) against the oracle —
    the Gram driver's off-diagonal blocks."""
    g1 = make_synthetic_dataset("nws", n_graphs=1, n_nodes=n1,
                                seed=seed, stop_prob=0.2)[0]
    g2 = make_synthetic_dataset("ba", n_graphs=1, n_nodes=n2,
                                seed=seed + 100, stop_prob=0.2)[0]
    res = mgk_pairs(batch_from_graphs([g1]), batch_from_graphs([g2]),
                    VK, SE, method="lowrank", tol=1e-12)
    np.testing.assert_allclose(float(res.values[0]),
                               mgk_direct(g1, g2, VK, SE), rtol=2e-3)
