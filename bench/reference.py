"""The plain reference: the same analytics, written straight from their
definitions with numpy and scipy.

It shares no code with the program and takes nothing the program made:
its adjacency is built here from the generator's edge list.  The lower
precision controls of ``bench/control.py`` are here too, beside the
computation they weaken.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np
import scipy.sparse as sp
from scipy.sparse import csgraph


class Adjacency:
    """The undirected simple graph of an edge list: both directions of
    every edge, duplicates merged, self-loops dropped, unit weights."""

    def __init__(self, src: np.ndarray, dst: np.ndarray, n: int):
        rows = np.concatenate([src, dst])
        cols = np.concatenate([dst, src])
        keep = rows != cols
        ones = np.ones(int(keep.sum()), np.float32)
        a = sp.csr_matrix((ones, (rows[keep], cols[keep])), shape=(n, n))
        a.sum_duplicates()
        a.data[:] = 1.0
        self.csr = a
        self.n = n
        self.degree = np.diff(a.indptr).astype(np.int64)

    @property
    def arcs(self) -> int:
        """Directed arcs: twice the undirected edges."""
        return int(self.csr.nnz)

    @property
    def edges(self) -> int:
        return self.arcs // 2

    @property
    def vertices_with_edge(self) -> int:
        return int(np.count_nonzero(self.degree))

    def reached_edges(self, hops: np.ndarray) -> int:
        """Undirected edges of the component a search reached (``hops``
        finite): every edge of a reached vertex lies in it."""
        return int(self.degree[np.isfinite(hops)].sum()) // 2


def bfs_hops(adj: Adjacency, root: int) -> np.ndarray:
    """Hop distance from ``root`` to every vertex, ``inf`` where
    unreached (float64)."""
    return csgraph.shortest_path(adj.csr, method="D", unweighted=True,
                                 indices=[int(root)])[0]


def bfs_hops_one_level_short(adj: Adjacency, root: int) -> np.ndarray:
    """The BFS control: the reference with its last level left out, as a
    search that stops one round early would leave it."""
    hops = bfs_hops(adj, root)
    reached = np.isfinite(hops)
    deepest = hops[reached].max()
    if deepest > 0:
        hops = np.where(hops == deepest, np.inf, hops)
    return hops


def pagerank(adj: Adjacency, damping: float, iterations: int,
             round_to=None) -> np.ndarray:
    """``iterations`` power iterations from the uniform vector, with the
    mass of dangling vertices spread evenly over all ``n`` (float64).

    ``round_to`` (a numpy dtype) rounds the rank and every intermediate
    vector to that type after each operation: the control's lower
    precision."""
    n = adj.n
    at = adj.csr.T.tocsr()
    deg = adj.degree.astype(np.float64)
    dangling = deg == 0
    inv = np.where(dangling, 0.0, 1.0 / np.maximum(deg, 1.0))

    def r(x):
        return x if round_to is None else x.astype(round_to).astype(np.float64)

    rank = r(np.full(n, 1.0 / n))
    for _ in range(iterations):
        contrib = r(rank * inv)
        pulled = r(at @ contrib)
        dmass = r(np.asarray(rank[dangling].sum()))
        rank = r((1.0 - damping) / n + damping * (pulled + dmass / n))
    return rank


def pagerank_bf16(adj: Adjacency, damping: float,
                  iterations: int) -> np.ndarray:
    """The PageRank control: the reference with every vector held in
    bfloat16, the precision below the float32 the program states."""
    return pagerank(adj, damping, iterations, round_to=ml_dtypes.bfloat16)
