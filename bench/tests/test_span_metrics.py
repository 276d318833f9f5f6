"""The metrics that read the program's own spans and counters: a traced
run of each cell at scale 10 on the CPU reports them as finite numbers,
and a program without those counters reports none of them."""

import math

import pytest

from bench import harness
from bench.tests import tiny

BFS, PR = "g500-22.bfs", "g500-22-tiered.pr"
NEW = {BFS: ("bfs_sparse_ns_per_slot", "bfs_dense_ns_per_slot"),
       PR: ("tier_fetch_exposed.pr", "tier_fetch_max_ms.pr")}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tiny"))


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: None)


@pytest.fixture(scope="module")
def traced(root):
    cache = {}

    def run(cell):
        if cell not in cache:
            cache[cell] = tiny.run(root, cell, trace=True)
        return cache[cell]
    return run


@pytest.mark.parametrize("cell, metric",
                         [(c, m) for c, ms in NEW.items() for m in ms])
def test_a_traced_run_reports_the_metric(traced, cell, metric):
    result = traced(cell)
    assert result["correct"]
    value = result["metrics"][metric]["value"]
    assert math.isfinite(value) and value >= 0


def test_the_exposed_fetch_time_is_at_most_all_fetch_time(traced):
    metrics = traced(PR)["metrics"]
    assert (metrics["tier_fetch_exposed.pr"]["value"]
            <= metrics["tier_io_wait.pr"]["value"])


@pytest.mark.parametrize("cell", [BFS, PR])
def test_a_program_without_the_counters_reports_nothing(root, cell):
    """The readers return nothing, and raise nothing, on stats that lack
    the counters they read (a program from before them)."""
    bench = harness.load_benchmark(root)
    stats = {"edges_touched": 10, "io_wait_us": 5, "h2d_bytes": 1}
    if cell == BFS:
        jobs = [{"kind": "bfs", "seconds": 1.0,
                 "searches": [{"stats": stats}]}]
    else:
        jobs = [{"kind": "pagerank", "seconds": 1.0, "iterations": 1,
                 "stats": stats}]
    run = harness.Run(cell=cell, config={}, traffic={}, seed=1, seconds=1,
                      jobs=jobs)
    names = {m["name"] for m in harness.cell_metrics(bench, cell, True)}
    for metric in NEW[cell]:
        assert metric in names
        assert harness.load_plugin(root, "metrics", metric).read(run) is None
