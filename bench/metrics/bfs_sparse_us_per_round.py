"""``bfs_sparse_us_per_round``: what one sparse round costs the host's
clock.  The engine's time from each sparse stretch's dispatch to the
blocking fetch that settles it (``RunStats.sparse_us``), over the sparse
rounds those stretches ran (``RunStats.sparse_rounds``, exact), summed
over the window's searches, in us.  The fixed cost a high-diameter search
pays once a level.  A program without these counters reports nothing."""


def read(run):
    stats = [s["stats"] for j in run.jobs if j["kind"] == "bfs"
             for s in j["searches"]]
    if not stats or "sparse_us" not in stats[0]:
        return None
    rounds = sum(st["sparse_rounds"] for st in stats)
    if not rounds:
        return None
    return sum(st["sparse_us"] for st in stats) / rounds
