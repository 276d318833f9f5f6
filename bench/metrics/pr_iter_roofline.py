"""``pr_iter_roofline``: the least time one PageRank pull iteration could
take on this chip, bound by HBM bytes (``peaks.pull_iteration_bytes``
over the published HBM bandwidth), over the device's compute time per
iteration in the trace (the union of its operations, copies from the host
left out)."""

from bench.peaks import pull_iteration_bytes


def read(run):
    jobs = [j for j in run.jobs if j["kind"] == "pagerank"]
    iters = sum(j["iterations"] for j in jobs)
    if not jobs or not iters or run.trace is None or run.peaks is None:
        return None
    compute_s = run.trace.compute_s
    if compute_s <= 0:
        return None
    least_s = (pull_iteration_bytes(run.extra["arcs"], run.extra["n"])
               / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (compute_s / iters)
