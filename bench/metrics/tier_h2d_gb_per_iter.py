"""``tier_h2d_gb_per_iter``: bytes the tiered store copied to the device
per PageRank iteration (``RunStats.h2d_bytes``, an exact count), in GB."""


def read(run):
    jobs = [j for j in run.jobs if j["kind"] == "pagerank"]
    iters = sum(j["iterations"] for j in jobs)
    h2d = sum(j["stats"]["h2d_bytes"] for j in jobs)
    if not iters or not h2d:
        return None
    return h2d / iters / 1e9
