"""``bfs_sparse_ns_per_slot``: what one edge slot of a sparse round costs.
The engine's host time from each sparse stretch's dispatch to the
blocking fetch that settles it (``RunStats.sparse_us``, the
``engine.stretch`` spans of regime ``sparse``), over the slots those
rounds charged (``RunStats.sparse_edges_touched``, exact), summed over the
window's searches, in ns.  A program without these counters reports
nothing."""


def read(run):
    stats = [s["stats"] for j in run.jobs if j["kind"] == "bfs"
             for s in j["searches"]]
    if not stats or "sparse_us" not in stats[0]:
        return None
    slots = sum(st["sparse_edges_touched"] for st in stats)
    if not slots:
        return None
    return 1e3 * sum(st["sparse_us"] for st in stats) / slots
