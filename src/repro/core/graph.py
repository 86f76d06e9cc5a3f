"""Graph representations for the marginalized graph kernel solver.

Two levels:

* :class:`Graph` — host-side (numpy) labeled weighted graph, the unit the
  data pipeline produces. Variable size.
* :class:`GraphBatch` — device-side (jnp) fixed-shape padded batch, the unit
  the solver consumes. Padding convention (see DESIGN.md §6): adjacency and
  edge labels are zero-padded, stopping probability ``q`` is zero-padded,
  degrees are one-padded, and the node mask marks real nodes. With that
  convention padded rows of the product system decouple into ``x_pad = 0``
  and contribute nothing to the kernel value.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Sequence

import numpy as np

import jax.numpy as jnp

from repro import obs

__all__ = ["Graph", "GraphBatch", "pad_graphs", "batch_from_graphs"]


@dataclasses.dataclass(frozen=True)
class Graph:
    """A host-side labeled, weighted, undirected graph.

    Attributes:
      adjacency: ``[n, n]`` float array of edge weights, symmetric,
        zero diagonal unless self loops are intended.
      edge_labels: ``[n, n]`` float array of edge labels; only entries where
        ``adjacency != 0`` are meaningful.
      vertex_labels: ``[n]`` array of vertex labels (float or int codes).
      start_prob: ``[n]`` starting probability of the random walk
        (defaults to uniform ``1/n``).
      stop_prob: ``[n]`` stopping probability of the random walk
        (defaults to a constant, paper uses values as small as 0.0005).
    """

    adjacency: np.ndarray
    edge_labels: np.ndarray
    vertex_labels: np.ndarray
    start_prob: np.ndarray
    stop_prob: np.ndarray

    @property
    def n_nodes(self) -> int:
        return int(self.adjacency.shape[0])

    @property
    def n_edges(self) -> int:
        return int(np.count_nonzero(np.triu(self.adjacency, k=1)))

    @staticmethod
    def create(
        adjacency: np.ndarray,
        edge_labels: np.ndarray | None = None,
        vertex_labels: np.ndarray | None = None,
        start_prob: np.ndarray | None = None,
        stop_prob: float | np.ndarray = 0.05,
    ) -> "Graph":
        adjacency = np.asarray(adjacency, dtype=np.float32)
        n = adjacency.shape[0]
        if adjacency.shape != (n, n):
            raise ValueError(f"adjacency must be square, got {adjacency.shape}")
        if not np.allclose(adjacency, adjacency.T):
            raise ValueError("adjacency must be symmetric (undirected graph)")
        if edge_labels is None:
            edge_labels = np.zeros_like(adjacency)
        edge_labels = np.asarray(edge_labels, dtype=np.float32)
        if vertex_labels is None:
            vertex_labels = np.zeros((n,), dtype=np.float32)
        vertex_labels = np.asarray(vertex_labels, dtype=np.float32)
        if start_prob is None:
            start_prob = np.full((n,), 1.0 / max(n, 1), dtype=np.float32)
        start_prob = np.asarray(start_prob, dtype=np.float32)
        if np.isscalar(stop_prob) or np.ndim(stop_prob) == 0:
            stop_prob = np.full((n,), float(stop_prob), dtype=np.float32)
        stop_prob = np.asarray(stop_prob, dtype=np.float32)
        return Graph(adjacency, edge_labels, vertex_labels, start_prob, stop_prob)

    def permuted(self, perm: np.ndarray) -> "Graph":
        """Return the graph with nodes reordered by ``perm`` (new <- old)."""
        perm = np.asarray(perm)
        inv = perm  # rows/cols gathered by perm
        return Graph(
            adjacency=self.adjacency[np.ix_(inv, inv)],
            edge_labels=self.edge_labels[np.ix_(inv, inv)],
            vertex_labels=self.vertex_labels[inv],
            start_prob=self.start_prob[inv],
            stop_prob=self.stop_prob[inv],
        )

    def degrees(self) -> np.ndarray:
        """Paper's degree definition: d_i = sum_j A_ij + q_i."""
        return self.adjacency.sum(axis=1) + self.stop_prob


class GraphBatch(NamedTuple):
    """Fixed-shape padded batch of graphs (a jax pytree).

    Shapes (B = batch, N = padded node count):
      adjacency    [B, N, N]   zero-padded
      edge_labels  [B, N, N]   zero-padded
      vertex_labels[B, N]      zero-padded (mask decides validity)
      start_prob   [B, N]      zero-padded
      stop_prob    [B, N]      zero-padded
      degrees      [B, N]      ONE-padded (keeps the padded diagonal SPD)
      node_mask    [B, N]      1.0 for real nodes
      n_nodes      [B]         int32 true node counts
    """

    adjacency: jnp.ndarray
    edge_labels: jnp.ndarray
    vertex_labels: jnp.ndarray
    start_prob: jnp.ndarray
    stop_prob: jnp.ndarray
    degrees: jnp.ndarray
    node_mask: jnp.ndarray
    n_nodes: jnp.ndarray

    @property
    def batch_size(self) -> int:
        return self.adjacency.shape[0]

    @property
    def padded_nodes(self) -> int:
        return self.adjacency.shape[1]


def pad_graphs(graphs: Sequence[Graph], pad_to: int | None = None,
               multiple_of: int = 8) -> dict[str, np.ndarray]:
    """Pad a list of graphs to a common node count (numpy, host side)."""
    max_n = max(g.n_nodes for g in graphs)
    if pad_to is None:
        pad_to = -(-max_n // multiple_of) * multiple_of
    if pad_to < max_n:
        raise ValueError(f"pad_to={pad_to} < largest graph ({max_n})")
    B, N = len(graphs), pad_to
    out = {
        "adjacency": np.zeros((B, N, N), np.float32),
        "edge_labels": np.zeros((B, N, N), np.float32),
        "vertex_labels": np.zeros((B, N), np.float32),
        "start_prob": np.zeros((B, N), np.float32),
        "stop_prob": np.zeros((B, N), np.float32),
        "degrees": np.ones((B, N), np.float32),
        "node_mask": np.zeros((B, N), np.float32),
        "n_nodes": np.zeros((B,), np.int32),
    }
    for b, g in enumerate(graphs):
        n = g.n_nodes
        out["adjacency"][b, :n, :n] = g.adjacency
        out["edge_labels"][b, :n, :n] = g.edge_labels
        out["vertex_labels"][b, :n] = g.vertex_labels
        out["start_prob"][b, :n] = g.start_prob
        out["stop_prob"][b, :n] = g.stop_prob
        out["degrees"][b, :n] = g.degrees()
        out["node_mask"][b, :n] = 1.0
        out["n_nodes"][b] = n
    return out


def batch_from_graphs(graphs: Sequence[Graph], pad_to: int | None = None,
                      multiple_of: int = 8) -> GraphBatch:
    arrs = pad_graphs(graphs, pad_to=pad_to, multiple_of=multiple_of)
    return GraphBatch(**{k: obs.to_device(v) for k, v in arrs.items()})
