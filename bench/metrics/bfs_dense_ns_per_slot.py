"""``bfs_dense_ns_per_slot``: what one edge slot of a dense round costs.
The engine's host time from each dense stretch's dispatch to the blocking
fetch that settles it (``RunStats.dense_us``, the ``engine.stretch``
spans of regime ``dense``), over the slots the dense rounds charged
(``edges_touched - sparse_edges_touched``, exact), summed over the
window's searches, in ns.  A program without these counters reports
nothing."""


def read(run):
    stats = [s["stats"] for j in run.jobs if j["kind"] == "bfs"
             for s in j["searches"]]
    if not stats or "dense_us" not in stats[0]:
        return None
    slots = sum(st["edges_touched"] - st["sparse_edges_touched"]
                for st in stats)
    if not slots:
        return None
    return 1e3 * sum(st["dense_us"] for st in stats) / slots
