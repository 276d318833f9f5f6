"""The harness finds configurations, traffic mixes and metric readers by
name, from files alone: a throwaway set in a temporary directory is
listed and loaded without any edit to the harness."""

import json
import textwrap
from pathlib import Path

import pytest

from bench import harness


@pytest.fixture
def throwaway(tmp_path):
    b = tmp_path / "bench"
    for d in ("configs", "workloads", "metrics"):
        (b / d).mkdir(parents=True)
    (b / "configs" / "toy-9.json").write_text(json.dumps(
        {"generator": "graph500", "scale": 9, "container": "resident"}))
    (b / "workloads" / "burst.json").write_text(json.dumps(
        {"job": "bfs", "roots": 3}))
    (b / "metrics" / "toy_rate.x.py").write_text(textwrap.dedent("""
        def read(run):
            return 2.0 * len(run.jobs)
        """))
    (b / "metrics" / "toy_silent.py").write_text(
        "def read(run):\n    return None\n")
    bench = {
        "configs": [{"name": "toy-9", "file": "bench/configs/toy-9.json"}],
        "workloads": [{"name": "toy-9.burst", "config": "toy-9",
                       "traffic": "burst", "chips": 1}],
        "end_to_end": [
            {"name": "toy_rate.x", "unit": "x", "workloads": ["toy-9.burst"]},
            {"name": "setup_s", "unit": "s"},
            {"name": "other_rate", "unit": "x", "workloads": ["elsewhere"]}],
        "per_layer": [
            {"name": "toy_silent", "unit": "%", "moves": "toy_rate.x"},
            {"name": "listed", "unit": "%", "moves": "other_rate",
             "workloads": ["toy-9.burst"]},
            {"name": "not_here", "unit": "%", "moves": "other_rate"}],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def test_lists_what_the_files_hold(throwaway):
    assert harness.list_names(throwaway, "configs") == ["toy-9"]
    assert harness.list_names(throwaway, "workloads") == ["burst"]
    assert harness.list_names(throwaway, "metrics") == ["toy_rate.x",
                                                        "toy_silent"]
    assert harness.list_names(throwaway, "jobs") == []


def test_loads_a_cell_by_name(throwaway):
    bench = harness.load_benchmark(throwaway)
    cell = harness.find_cell(bench, "toy-9.burst")
    assert harness.cell_config(throwaway, bench, cell)["scale"] == 9
    traffic = harness.load_data(throwaway, "workloads", cell["traffic"])
    assert traffic == {"job": "bfs", "roots": 3}
    with pytest.raises(KeyError):
        harness.find_cell(bench, "missing")
    with pytest.raises(FileNotFoundError):
        harness.load_data(throwaway, "workloads", "missing")


def test_loads_a_metric_reader_by_name(throwaway):
    run = harness.Run(cell="toy-9.burst", config={}, traffic={}, seed=1,
                      seconds=1, jobs=[{}, {}, {}])
    reader = harness.load_plugin(throwaway, "metrics", "toy_rate.x")
    assert reader.read(run) == 6.0
    assert harness.load_plugin(throwaway, "metrics",
                               "toy_silent").read(run) is None
    with pytest.raises(FileNotFoundError):
        harness.load_plugin(throwaway, "metrics", "missing")


def test_picks_the_cells_metrics(throwaway):
    bench = harness.load_benchmark(throwaway)
    names = lambda ms: [m["name"] for m in ms]
    assert names(harness.cell_metrics(bench, "toy-9.burst", False)) == [
        "toy_rate.x", "setup_s"]
    assert names(harness.cell_metrics(bench, "toy-9.burst", True)) == [
        "toy_silent", "listed"]


def test_the_repos_own_benchmark_resolves():
    """Every cell of BENCHMARK.json names files that exist, and every
    metric has a reader."""
    root = Path(__file__).resolve().parents[2]
    bench = harness.load_benchmark(root)
    have = set(harness.list_names(root, "metrics"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert m["name"] in have
    for cell in bench["workloads"]:
        config = harness.cell_config(root, bench, cell)
        traffic = harness.load_data(root, "workloads", cell["traffic"])
        harness.load_plugin(root, "generators", config["generator"])
        assert (harness.bench_dir(root) / "containers"
                / f"{config['container']}.py").is_file()
        assert (harness.bench_dir(root) / "jobs"
                / f"{traffic['job']}.py").is_file()
        assert harness.cell_metrics(bench, cell["name"], True)
