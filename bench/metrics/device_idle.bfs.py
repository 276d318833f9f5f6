"""``device_idle.bfs``: the share of the traced window of BFS searches in
which no operation ran on the device."""

from bench.trace_reduce import idle_percent


def read(run):
    if any(j["kind"] == "bfs" for j in run.jobs):
        return idle_percent(run.trace)
    return None
