"""Plain data types and file lookup shared by the benchmark's modules.

Nothing here imports the program under test: the generators and the
reference work on :class:`PlainGraph`, and the harness converts each one
to the program's own graph type.
"""
from __future__ import annotations

import importlib.util
import os
from typing import NamedTuple

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))


class PlainGraph(NamedTuple):
    """A labeled undirected graph as numpy arrays (paper Sec. II):
    ``[n, n]`` symmetric edge weights and edge labels, ``[n]`` vertex
    labels, start and stop probabilities of the random walk."""
    adjacency: np.ndarray
    edge_labels: np.ndarray
    vertex_labels: np.ndarray
    start_prob: np.ndarray
    stop_prob: np.ndarray

    @property
    def n_nodes(self) -> int:
        return int(self.adjacency.shape[0])

    @property
    def nnz(self) -> int:
        """Nonzeros of the adjacency matrix (both directions)."""
        return int(np.count_nonzero(self.adjacency))


def plain_graph(adjacency, edge_labels, vertex_labels,
                stop_prob: float) -> PlainGraph:
    """Uniform start probability 1/n and a constant stop probability, in
    float32 as the generators hand them on."""
    n = adjacency.shape[0]
    return PlainGraph(
        np.asarray(adjacency, np.float32),
        np.asarray(edge_labels, np.float32),
        np.asarray(vertex_labels, np.float32),
        np.full((n,), 1.0 / max(n, 1), np.float32),
        np.full((n,), stop_prob, np.float32))


def load_module(kind: str, name: str, bench: str = BENCH):
    """The module ``<bench>/<kind>/<name>.py``: generators, base kernels
    and metric readers are found by the name ``BENCHMARK.json`` or a
    configuration gives them, so adding one needs only a new file."""
    path = os.path.join(bench, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
