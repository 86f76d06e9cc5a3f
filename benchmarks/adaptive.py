"""Paper Fig. 8 analog: dense vs block-sparse XMV crossover by tile
occupancy. Both kernels run in the same (interpret) mode so the relative
ordering is meaningful; the derived column reports the work-model ratio
(active tile products vs all tile products) that the production dispatch
uses to pick a primitive."""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from repro.core.base_kernels import SquareExponential
from repro.core.octile import octile_decompose
from repro.kernels.xmv_block_sparse import pack_graph, xmv_block_sparse
from repro.kernels.xmv_block_sparse import to_tiles
from repro.kernels.xmv_dense import DENSE_TILE, xmv_dense
from .common import row, time_fn

EK = SquareExponential(1.0, rank=10)


def _graph_with_density(rng, n, target_nnz_per_tile):
    """Random graph whose non-empty octiles hold ~target nnz each."""
    a = np.zeros((n, n), np.float32)
    nt = n // 8
    for ti in range(nt):
        for tj in range(ti, nt):
            if rng.random() < 0.35:      # ~1/3 of tiles non-empty
                k = min(64, max(1, int(rng.normal(target_nnz_per_tile, 2))))
                idx = rng.choice(64, size=k, replace=False)
                for f in idx:
                    i, j = ti * 8 + f // 8, tj * 8 + f % 8
                    a[i, j] = a[j, i] = 1.0
    e = rng.random((n, n)).astype(np.float32) * (a != 0)
    return a, e


def run(n: int = 64, occupancies=(2, 8, 16, 32, 56)) -> list[str]:
    rng = np.random.default_rng(0)
    out = []
    for occ in occupancies:
        A, E = _graph_with_density(rng, n, occ)
        P = jnp.asarray(rng.random((n, n), np.float32))
        Aj, Ej = jnp.asarray(A), jnp.asarray(E)
        us_d = time_fn(lambda a, e, p: xmv_dense(a, e, a, e, p, EK),
                       Aj, Ej, to_tiles(P, DENSE_TILE), iters=3)
        p1 = pack_graph(A, E)
        us_s = time_fn(lambda pk, p: xmv_block_sparse(pk, pk, p, EK),
                       p1, P, iters=3)
        oset = octile_decompose(A, E)
        frac = oset.n_nonempty / max((n // 8) ** 2, 1)
        work_ratio = frac ** 2      # tile-pair products touched
        winner = "sparse" if us_s < us_d else "dense"
        out.append(row(f"adaptive_occ{occ}", min(us_d, us_s),
                       f"dense_us={us_d:.0f};sparse_us={us_s:.0f};"
                       f"work_ratio={work_ratio:.3f};winner={winner}"))
    return out


if __name__ == "__main__":
    run()
