"""``bfs_rounds_per_sync``: rounds the engine ran per blocking host fetch
that settled a stretch (``RunStats.rounds`` over ``RunStats.stretches``),
summed over the window's searches.  1 is one sync a round; fused
stretches raise it.  A program without the ``stretches`` counter reports
nothing."""


def read(run):
    stats = [s["stats"] for j in run.jobs if j["kind"] == "bfs"
             for s in j["searches"]]
    if not stats or "stretches" not in stats[0]:
        return None
    syncs = sum(st["stretches"] for st in stats)
    if not syncs:
        return None
    return sum(st["rounds"] for st in stats) / syncs
