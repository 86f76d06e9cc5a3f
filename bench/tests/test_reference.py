"""The plain reference agrees with the program's dense oracle
(``core/reference.py:mgk_direct``) on small pairs."""
import functools

import pytest

import reference
from plain import load_module
from repro.core import Graph, KroneckerDelta, SquareExponential
from repro.core.reference import mgk_direct


def kernels():
    kd = load_module("base_kernels", "KroneckerDelta").kappa
    se = load_module("base_kernels", "SquareExponential").kappa
    return (functools.partial(kd, h=0.5, n_labels=8),
            functools.partial(se, alpha=1.0))


def program_graph(g):
    return Graph.create(g.adjacency, g.edge_labels, g.vertex_labels,
                        g.start_prob, g.stop_prob)


def small_graphs(kind: str, seed: int):
    if kind == "nws":
        params = {"n_graphs": 3, "n_nodes": 24, "k": 3, "p": 0.1,
                  "n_vertex_labels": 8, "structure_seed": seed}
    else:
        params = {"sizes": [5, 17, 31]}
    return load_module("generators", kind).make(params, seed, 0.05)


@pytest.mark.parametrize("kind", ["nws", "drugbank"])
@pytest.mark.parametrize("seed", [0, 1])
def test_reference_agrees_with_the_dense_oracle(kind, seed):
    kv, ke = kernels()
    graphs = small_graphs(kind, seed)
    for a, b in [(0, 1), (1, 2), (2, 2)]:
        ours = reference.mgk(graphs[a], graphs[b], kv, ke)
        # the oracle evaluates the kernels in float32
        theirs = mgk_direct(program_graph(graphs[a]),
                            program_graph(graphs[b]),
                            KroneckerDelta(0.5, 8), SquareExponential(1.0))
        assert abs(ours - theirs) <= 1e-6 * abs(ours)


def test_reference_refuses_a_solve_it_cannot_finish(monkeypatch):
    kv, ke = kernels()
    g = small_graphs("nws", 0)
    monkeypatch.setattr(reference, "MAX_ITER", 2)
    with pytest.raises(reference.ReferenceError):
        reference.mgk(g[0], g[1], kv, ke)
