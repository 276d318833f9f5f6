"""``bfs_mteps``: the Graph500 rate.  The undirected edges of the
component each search traversed, summed over the window's searches, over
the summed time of the jobs (batches of searches) that ran them, in
millions per second."""


def read(run):
    jobs = [j for j in run.jobs if j["kind"] == "bfs"]
    if not jobs:
        return None
    return sum(j["work"] for j in jobs) / sum(j["seconds"] for j in jobs) / 1e6
