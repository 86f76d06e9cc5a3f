"""Newman-Watts-Strogatz small-world graphs (paper Sec. VI-A).

A copy of ``repro.data.synthetic`` as it stood when the benchmark was
written, draw for draw, so that a change to the program cannot move the
data the benchmark measures on. ``params``: ``n_graphs``, ``n_nodes``,
``k``, ``p``, ``n_vertex_labels``, ``structure_seed``; edge labels are
U[0, 1].

The graphs' structure is drawn once, from ``structure_seed``; ``--seed``
draws their labels. The sparse kernels' work follows the structure (the
octile slots of each pack), so every seed then gives the program the
same shapes and the same work, on other labels.
"""
from __future__ import annotations

import numpy as np

from plain import PlainGraph, plain_graph


def newman_watts_strogatz(n: int, k: int, p: float, *,
                          rng: np.random.Generator, n_vertex_labels: int,
                          stop_prob: float) -> PlainGraph:
    """Ring lattice of degree 2k plus about n*k*p random shortcuts."""
    adj = np.zeros((n, n), np.float32)
    idx = np.arange(n)
    for off in range(1, k + 1):
        adj[idx, (idx + off) % n] = 1.0
    for _ in range(int(rng.binomial(n * k, p))):
        u, v = rng.integers(0, n, size=2)
        if u != v:
            adj[u, v] = 1.0
    adj = np.maximum(adj, adj.T)
    np.fill_diagonal(adj, 0.0)
    return plain_graph(adj, *_labels(adj, rng, n_vertex_labels), stop_prob)


def _labels(adj, rng: np.random.Generator, n_vertex_labels: int):
    n = adj.shape[0]
    labels = np.triu(rng.uniform(0.0, 1.0, size=(n, n)).astype(np.float32),
                     1)
    labels = (labels + labels.T) * (adj != 0)
    return labels, rng.integers(0, n_vertex_labels, size=n)


def make(params: dict, seed: int, stop_prob: float) -> list[PlainGraph]:
    shapes = np.random.default_rng(params["structure_seed"])
    labels = np.random.default_rng(seed)
    nvl = params["n_vertex_labels"]
    out = []
    for _ in range(params["n_graphs"]):
        g = newman_watts_strogatz(params["n_nodes"], params["k"],
                                  params["p"], rng=shapes,
                                  n_vertex_labels=nvl, stop_prob=stop_prob)
        out.append(plain_graph(g.adjacency,
                               *_labels(g.adjacency, labels, nvl),
                               stop_prob))
    return out
