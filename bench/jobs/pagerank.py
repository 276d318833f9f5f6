"""The ``pagerank`` job: LDBC Graphalytics PageRank, a fixed number of
power iterations, through the program's ``pagerank.pr_pull``.

Each job starts from the uniform vector and runs ``iterations`` pull
iterations with ``damping`` (``tol=0``, so none stops early).  Warm-up is
one iteration, which compiles every program a job runs.  After the window
every job's whole rank vector is compared with the float64 reference, by
the largest relative gap over the vertices.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from bench import reference
from bench.harness import Check, EdgeList
from repro.core.algorithms import pagerank as program_pagerank


class Job:
    kind = "pagerank"

    def __init__(self, graph, edges: EdgeList, traffic: dict, seed: int,
                 run):
        self.g = graph
        self.n = edges.n
        self.run = run
        self.iterations = int(traffic["iterations"])
        self.damping = float(traffic["damping"])
        self.limit = float(traffic["max_rel_err"])

    def _pagerank(self, iterations: int):
        return program_pagerank.pr_pull(self.g, damping=self.damping,
                                        tol=0.0, max_iters=iterations)

    def warm_up(self) -> None:
        rank, _ = self._pagerank(1)
        rank.block_until_ready()

    def run_one(self) -> dict:
        t0 = time.perf_counter()
        rank, stats = self._pagerank(self.iterations)
        rank.block_until_ready()
        t1 = time.perf_counter()
        return {"kind": self.kind, "t0": t0, "t1": t1, "seconds": t1 - t0,
                "iterations": int(stats.rounds), "stats": stats.as_dict(),
                "out": rank}

    def collect(self) -> None:
        """Bring every job's ranks to the host and let go of the device
        state."""
        for job in self.run.jobs:
            job["out"] = np.asarray(jax.device_get(job["out"]))[: self.n]
        self.g = None

    def check(self, edges: EdgeList) -> list[Check]:
        adj = reference.Adjacency(edges.src, edges.dst, edges.n)
        self.run.extra.update(arcs=adj.arcs, n=edges.n)
        want = reference.pagerank(adj, self.damping, self.iterations)
        worst = 0.0
        for job in self.run.jobs:
            job["work"] = adj.vertices_with_edge + adj.edges
            err = max_rel_err(job.pop("out"), want)
            job["correct"] = bool(err <= self.limit)
            worst = max(worst, err)
        return [Check("pr_max_rel_err", worst, self.limit)]


def max_rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """Largest ``|got - want| / want`` over the vertices (every reference
    rank is at least (1 - damping) / n, so never 0); NaN counts as
    infinitely wrong."""
    gap = np.abs(got.astype(np.float64) - want) / want
    return float(np.inf) if np.isnan(gap).any() else float(gap.max())
