"""``pr_mevps``: the Graphalytics rate.  Vertices with an edge plus
undirected edges, per job, summed over the window's jobs, over their
summed time, in millions per second."""


def read(run):
    jobs = [j for j in run.jobs if j["kind"] == "pagerank"]
    if not jobs:
        return None
    return sum(j["work"] for j in jobs) / sum(j["seconds"] for j in jobs) / 1e6
