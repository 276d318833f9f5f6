#!/usr/bin/env python3
"""The controls of the correctness checks, at a cell's own size.

A control is the plain reference put in the program's place in a weaker
form, compared with the full reference by the cell's own check.  It has to
come out not correct, or the check could not tell a broken program from a
sound one:

* BFS (exact hops; no precision to lower): the reference with its last
  level left out, as a search that stops one round early leaves it;
* PageRank (float32 stated): the reference with every vector rounded to
  bfloat16, the precision below.

    python3 bench/control.py --workload g500-22.bfs --seeds 1 2 3

prints one JSON line per seed with the control's reading beside the
cell's limit.  The benchmark's own runs do not run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

from bench import harness, reference  # noqa: E402


def bfs_reading(edges, traffic: dict, seed: int) -> dict:
    from bench.jobs.bfs import draw_roots, hops_wrong
    adj = reference.Adjacency(edges.src, edges.dst, edges.n)
    wrong = [hops_wrong(reference.bfs_hops_one_level_short(adj, r),
                        reference.bfs_hops(adj, r))
             for r in draw_roots(edges, int(traffic["roots"]), seed)]
    return {"check": "hops_wrong", "value": sum(wrong), "per_root": wrong,
            "limit": 0}


def pagerank_reading(edges, traffic: dict, seed: int) -> dict:
    from bench.jobs.pagerank import max_rel_err
    adj = reference.Adjacency(edges.src, edges.dst, edges.n)
    d, k = float(traffic["damping"]), int(traffic["iterations"])
    err = max_rel_err(reference.pagerank_bf16(adj, d, k),
                      reference.pagerank(adj, d, k))
    return {"check": "pr_max_rel_err", "value": err,
            "limit": float(traffic["max_rel_err"])}


READINGS = {"bfs": bfs_reading, "pagerank": pagerank_reading}


def control(root: Path, cell_name: str, seed: int) -> dict:
    """The control's reading for ``cell_name`` at ``seed``, with the
    check's limit: the control fails where the reading exceeds it."""
    bench = harness.load_benchmark(root)
    cell = harness.find_cell(bench, cell_name)
    config = harness.cell_config(root, bench, cell)
    traffic = harness.load_data(root, "workloads", cell["traffic"])
    edges = harness.load_plugin(root, "generators",
                                config["generator"]).generate(seed, config)
    out = READINGS[traffic["job"]](edges, traffic, seed)
    out.update(cell=cell_name, seed=seed, fails=out["value"] > out["limit"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        print(json.dumps(control(ROOT, args.workload, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
