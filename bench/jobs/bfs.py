"""The ``bfs`` job: Graph500 kernel 2 through the program's
``bfs.bfs_dd_sparse``.

One job is a batch of ``roots`` searches, one after another.  The roots
are vertices with an edge (as the Graph500 specification draws its search
keys), drawn once from the structure's seed, so that every run searches
from the same vertices of the same structure under its own vertex names
and in its own order.  What a search costs depends on its root (the
program's sparse ladder picks its rungs from the frontier's size), so
roots drawn per seed would make the work itself change from run to run.

Warm-up runs the batch once, which compiles every program the window's
batches run.  After the window every search's hop distances are compared
with scipy's, vertex by vertex.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from bench import reference
from bench.harness import Check, EdgeList, seed_rng
from repro.core.algorithms import bfs as program_bfs

ROOTS_TAG = 0xB0
ORDER_TAG = 0xB1


def draw_roots(edges: EdgeList, count: int, seed: int) -> np.ndarray:
    """``count`` distinct vertices with an edge other than a self-loop:
    the same structural vertices for every seed (drawn from the structure
    seed), under this run's names, in an order drawn from ``seed``."""
    keep = edges.src != edges.dst
    deg = (np.bincount(edges.src[keep], minlength=edges.n)
           + np.bincount(edges.dst[keep], minlength=edges.n))
    cand = np.flatnonzero(deg[edges.labels] > 0)
    picked = seed_rng(edges.structure_seed, ROOTS_TAG).choice(
        cand, count, replace=False)
    return edges.labels[picked[seed_rng(seed, ORDER_TAG).permutation(count)]]


class Job:
    kind = "bfs"

    def __init__(self, graph, edges: EdgeList, traffic: dict, seed: int,
                 run):
        self.g = graph
        self.n = edges.n
        self.run = run
        self.roots = draw_roots(edges, int(traffic["roots"]), seed)

    def _bfs(self, root: int):
        return program_bfs.bfs_dd_sparse(self.g, int(root))

    def warm_up(self) -> None:
        for root in self.roots:
            dist, _ = self._bfs(root)
            dist.block_until_ready()

    def run_one(self) -> dict:
        searches = []
        t0 = time.perf_counter()
        for root in self.roots:
            s0 = time.perf_counter()
            dist, stats = self._bfs(root)
            dist.block_until_ready()
            searches.append({"root": int(root), "out": dist,
                             "seconds": time.perf_counter() - s0,
                             "stats": stats.as_dict()})
        t1 = time.perf_counter()
        return {"kind": self.kind, "t0": t0, "t1": t1, "seconds": t1 - t0,
                "searches": searches}

    def collect(self) -> None:
        """Bring every search's distances to the host and let go of the
        device state."""
        for job in self.run.jobs:
            for s in job["searches"]:
                s["out"] = np.asarray(jax.device_get(s["out"]))[: self.n]
        self.g = None

    def check(self, edges: EdgeList) -> list[Check]:
        adj = reference.Adjacency(edges.src, edges.dst, edges.n)
        want = {}
        wrong = 0
        for job in self.run.jobs:
            bad_job = 0
            for s in job["searches"]:
                if s["root"] not in want:
                    want[s["root"]] = reference.bfs_hops(adj, s["root"])
                hops = want[s["root"]]
                s["work"] = adj.reached_edges(hops)
                bad_job += hops_wrong(program_hops(s.pop("out")), hops)
            job["work"] = sum(s["work"] for s in job["searches"])
            job["arcs"] = 2 * job["work"]
            job["edges_touched"] = sum(s["stats"]["edges_touched"]
                                       for s in job["searches"])
            job["correct"] = bad_job == 0
            wrong += bad_job
        return [Check("hops_wrong", wrong, 0)]


def program_hops(dist: np.ndarray) -> np.ndarray:
    """The program's distances as float64 hops, ``inf`` where unreached
    (the program marks those with its float32 maximum)."""
    got = dist.astype(np.float64)
    return np.where(got >= float(program_bfs.INF), np.inf, got)


def hops_wrong(got: np.ndarray, want: np.ndarray) -> int:
    """Vertices whose hop distance differs from the reference's (an
    unreached vertex has distance ``inf`` on both sides)."""
    return int(np.count_nonzero(got != want))
