"""DrugBank-shaped molecular graphs (paper Sec. VI-B).

A copy of ``repro.data.molecules`` as it stood when the benchmark was
written, draw for draw: a random tree with ring closures, bond orders
{1, 1.5, 2, 3}/3 as edge labels and element codes 0-7 as vertex labels.

The benchmark fixes the molecule sizes in the configuration
(``params["sizes"]``) and draws only bonds and elements from the seed:
the program's buckets, and so its compiled block shapes, follow the
sizes, and a size drawn per seed would make every run compile anew.
"""
from __future__ import annotations

import numpy as np

from plain import PlainGraph, plain_graph

_BOND_ORDERS = np.array([1.0, 1.5, 2.0, 3.0], np.float32) / 3.0
_BOND_PROBS = np.array([0.70, 0.15, 0.12, 0.03])
_ELEMENT_PROBS = [0.45, 0.25, 0.12, 0.08, 0.04, 0.03, 0.02, 0.01]


def drugbank_like_graph(n_atoms: int, *, rng: np.random.Generator,
                        stop_prob: float) -> PlainGraph:
    adj = np.zeros((n_atoms, n_atoms), np.float32)
    lab = np.zeros((n_atoms, n_atoms), np.float32)
    for i in range(1, n_atoms):
        j = i - 1 if rng.random() < 0.7 else int(rng.integers(0, i))
        order = rng.choice(_BOND_ORDERS, p=_BOND_PROBS)
        adj[i, j] = adj[j, i] = 1.0
        lab[i, j] = lab[j, i] = order
    for _ in range(max(0, n_atoms // 6)):          # ring closures
        u, v = rng.integers(0, n_atoms, size=2)
        if u != v and adj[u, v] == 0:
            adj[u, v] = adj[v, u] = 1.0
            lab[u, v] = lab[v, u] = 1.0
    elements = rng.choice(np.arange(8, dtype=np.float32), p=_ELEMENT_PROBS,
                          size=n_atoms)
    return plain_graph(adj, lab, elements, stop_prob)


def draw_size(rng: np.random.Generator, max_atoms: int = 551) -> int:
    """One size from the log-normal(3.3, 0.7) clipped to 2..max_atoms."""
    return int(np.clip(rng.lognormal(mean=3.3, sigma=0.7), 2, max_atoms))


def make_drugbank_like_dataset(n_graphs: int, seed: int,
                               stop_prob: float = 0.05,
                               max_atoms: int = 551) -> list[PlainGraph]:
    """Sizes and bonds from one seed, as the program's generator draws
    them; the fixed size list of the configuration came from seed 0."""
    rng = np.random.default_rng(seed)
    return [drugbank_like_graph(draw_size(rng, max_atoms), rng=rng,
                                stop_prob=stop_prob)
            for _ in range(n_graphs)]


def make(params: dict, seed: int, stop_prob: float) -> list[PlainGraph]:
    rng = np.random.default_rng(seed)
    return [drugbank_like_graph(int(n), rng=rng, stop_prob=stop_prob)
            for n in params["sizes"]]
