"""``device_idle.pr``: the share of the traced window of PageRank jobs in
which no operation ran on the device."""

from bench.trace_reduce import idle_percent


def read(run):
    if any(j["kind"] == "pagerank" for j in run.jobs):
        return idle_percent(run.trace)
    return None
