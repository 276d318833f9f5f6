"""``tier_fetch_exposed.pr``: the tiered store's misses that began with
the device drained of their relax (``RunStats.fetch_exposed_us``: the
relax's newest device value already ready as the ``tier.fetch`` span
opened), as a share of the PageRank jobs' wall time.  A lower bound on
the device idle time the fetches cause.  A program without the counter
reports nothing."""


def read(run):
    jobs = [j for j in run.jobs if j["kind"] == "pagerank"]
    if not jobs or "fetch_exposed_us" not in jobs[0]["stats"]:
        return None
    exposed = sum(j["stats"]["fetch_exposed_us"] for j in jobs) / 1e6
    return 100.0 * exposed / sum(j["seconds"] for j in jobs)
