"""``tier_fetch_max_ms.pr``: the longest single miss of the tiered store
(one ``tier.fetch`` span: host read, CRC32 and device_put issue) over the
window's PageRank jobs (``RunStats.fetch_max_us``, each job's own
maximum), in ms.  A program without the counter reports nothing."""


def read(run):
    jobs = [j for j in run.jobs if j["kind"] == "pagerank"]
    if not jobs or "fetch_max_us" not in jobs[0]["stats"]:
        return None
    return max(j["stats"]["fetch_max_us"] for j in jobs) / 1e3
