"""``tier_io_wait.pr``: the tiered store's fetch time (``io_wait_us``:
host read, CRC32 and the H2D issue of every missed shard) as a share of
the PageRank jobs' wall time."""


def read(run):
    jobs = [j for j in run.jobs if j["kind"] == "pagerank"]
    wait = sum(j["stats"]["io_wait_us"] for j in jobs) / 1e6
    if not jobs or not wait:
        return None
    return 100.0 * wait / sum(j["seconds"] for j in jobs)
