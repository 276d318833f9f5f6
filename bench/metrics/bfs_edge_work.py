"""``bfs_edge_work``: edge slots the engine processed (its exact
``RunStats.edges_touched``) per arc of the traversed components, over the
window's searches.  1 is one look at every arc; the sparse ladder's
budgets and dense rounds add to it."""


def read(run):
    jobs = [j for j in run.jobs if j["kind"] == "bfs"]
    arcs = sum(j["arcs"] for j in jobs)
    if not arcs:
        return None
    return sum(j["edges_touched"] for j in jobs) / arcs
