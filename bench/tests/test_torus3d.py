"""The ``torus3d`` configuration (Ligra's 3D-grid) at sides a CPU test can
hold: the generator's structure and names, the program's BFS against the
closed form and scipy on the fused and per-round paths, the stretch
counter on a search of many levels, the two engine metrics' readers, and
the cell run through the harness."""

import json
import math

import numpy as np
import pytest

from bench import harness, reference
from bench.jobs.bfs import program_hops
from bench.tests import tiny
from bench.torus_reference import torus_hops
from repro.core import frontier as fr
from repro.core import from_coo
from repro.core.algorithms import bfs as program_bfs

CELL = "torus3d-216.bfs"
# side -> a block that divides side**3
BLOCKS = {8: 512, 9: 27, 12: 64}
SEEDS = (1, 2**31 + 5)
BLOCK = 64


def generate(side, seed=SEEDS[0]):
    gen = harness.load_plugin(tiny.REPO, "generators", "torus3d")
    return gen.generate(seed, {"side": side, "relabel_block": BLOCKS[side],
                               "structure_seed": side})


def named(hops_by_structure, labels):
    """Structural-order hops, re-indexed by this run's vertex names."""
    out = np.empty_like(hops_by_structure)
    out[labels] = hops_by_structure
    return out


@pytest.mark.parametrize("side", sorted(BLOCKS))
def test_every_vertex_has_six_distinct_neighbours(side):
    e = generate(side)
    n = side ** 3
    assert e.n == n and len(e.src) == len(e.dst) == 3 * n
    assert not np.any(e.src == e.dst)
    pairs = np.minimum(e.src, e.dst).astype(np.int64) * n + np.maximum(
        e.src, e.dst)
    assert np.unique(pairs).size == 3 * n
    deg = np.bincount(e.src, minlength=n) + np.bincount(e.dst, minlength=n)
    assert np.all(deg == 6)


@pytest.mark.parametrize("side", sorted(BLOCKS))
def test_the_structure_is_the_same_for_every_seed(side):
    gen = harness.load_plugin(tiny.REPO, "generators", "torus3d")
    want_src, want_dst = gen.torus_edges(side)
    names = []
    for seed in SEEDS:
        e = generate(side, seed)
        structural = np.argsort(e.labels)
        np.testing.assert_array_equal(structural[e.src], want_src)
        np.testing.assert_array_equal(structural[e.dst], want_dst)
        names.append(e.labels)
    assert not np.array_equal(*names)


@pytest.mark.parametrize("side", sorted(BLOCKS))
def test_labels_permute_ids_within_their_block(side):
    block, n = BLOCKS[side], side ** 3
    labels = generate(side).labels
    np.testing.assert_array_equal(np.sort(labels), np.arange(n))
    np.testing.assert_array_equal(labels // block, np.arange(n) // block)


def test_a_side_the_block_does_not_divide_is_refused():
    gen = harness.load_plugin(tiny.REPO, "generators", "torus3d")
    with pytest.raises(ValueError):
        gen.generate(1, {"side": 10, "relabel_block": 512,
                         "structure_seed": 10})


def _program(e):
    """The program's graph of ``e`` at a small block size: a search then
    climbs and descends several rungs of both ladders."""
    return from_coo(e.src, e.dst, e.n, symmetrize=True, block_size=BLOCK)


def _hops(g, e, root, fused=True):
    dist, st = program_bfs.bfs_dd_sparse(g, int(e.labels[root]),
                                         fused=fused)
    return program_hops(np.asarray(dist)[: e.n]), st


@pytest.mark.parametrize("side", [9, 12], ids=["odd-side", "even-side"])
def test_bfs_equals_the_closed_form_and_scipy(side):
    e = generate(side)
    root = 5 * side + 3
    want = named(torus_hops(side, root), e.labels)
    adj = reference.Adjacency(e.src, e.dst, e.n)
    np.testing.assert_array_equal(
        want, reference.bfs_hops(adj, e.labels[root]))
    got, st = _hops(_program(e), e, root)
    np.testing.assert_array_equal(got, want)
    assert st.rounds == st.sparse_rounds == want.max() + 1
    assert st.dense_rounds == 0


def test_stretches_follow_the_rung_switches():
    """Fused, one blocking fetch per run of rounds on one (capacity,
    budget) rung; per round, one a round.  The torus's level sizes give
    the rungs in closed form.  Both paths give the closed form's hops."""
    side = 12
    e = generate(side)
    g = _program(e)
    sizes = np.bincount(torus_hops(side, 0))
    caps = fr.ladder_capacities(g.n_pad, g.block_size)
    budgets = fr.ladder_capacities(g.m_pad, g.block_size)
    rungs = [(fr.pick_capacity(int(c), caps),
              fr.pick_capacity(int(6 * c), budgets)) for c in sizes]
    switches = sum(a != b for a, b in zip(rungs, rungs[1:]))
    assert switches >= 4
    want = named(torus_hops(side, 0), e.labels)
    fused_hops, fused = _hops(g, e, 0)
    per_round_hops, per_round = _hops(g, e, 0, fused=False)
    np.testing.assert_array_equal(fused_hops, want)
    np.testing.assert_array_equal(per_round_hops, want)
    assert fused.rounds == per_round.rounds == len(sizes)
    assert fused.dense_rounds == per_round.dense_rounds == 0
    assert fused.stretches == switches + 1 < fused.rounds
    assert per_round.stretches == per_round.rounds


def _run(searches):
    jobs = [{"kind": "bfs", "seconds": 1.0, "searches": searches},
            {"kind": "pagerank", "seconds": 1.0, "iterations": 1,
             "stats": {"rounds": 1}}]
    return harness.Run(cell=CELL, config={}, traffic={}, seed=1, seconds=1,
                       jobs=jobs)


def _reader(name):
    return harness.load_plugin(tiny.REPO, "metrics", name).read


def test_the_engine_readers_sum_over_the_searches():
    stats = [{"rounds": 325, "stretches": 19, "sparse_rounds": 325,
              "sparse_us": 30_000_000},
             {"rounds": 7, "stretches": 6, "sparse_rounds": 5,
              "sparse_us": 500_000}]
    run = _run([{"stats": s} for s in stats])
    assert _reader("bfs_rounds_per_sync")(run) == 332 / 25
    assert _reader("bfs_sparse_us_per_round")(run) == 30_500_000 / 330


@pytest.mark.parametrize("stats", [
    {"rounds": 3, "sparse_rounds": 3},
    {"rounds": 0, "stretches": 0, "sparse_rounds": 0, "sparse_us": 0},
], ids=["without-counters", "no-rounds"])
def test_the_engine_readers_report_nothing_without_counts(stats):
    for name in ("bfs_rounds_per_sync", "bfs_sparse_us_per_round"):
        assert _reader(name)(_run([{"stats": stats}])) is None
        assert _reader(name)(_run([])) is None


def test_the_cell_runs_through_the_harness(tmp_path, monkeypatch):
    """The cell's own files, with the side cut to 12: the traced run is
    correct, compiles nothing in its window and reports every metric the
    cell lists."""
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: None)
    root = tiny.make_root(tmp_path)
    path = root / "bench" / "configs" / "torus3d-216.json"
    config = json.loads(path.read_text())
    config.update(side=12, relabel_block=BLOCKS[12])
    path.write_text(json.dumps(config))
    result = tiny.run(root, CELL, seed=2**31 + 7, trace=True)
    assert result["correct"] and result["window_compiles"] == 0
    metrics = result["metrics"]
    for name in ("bfs_edge_work", "bfs_sparse_ns_per_slot",
                 "bfs_sparse_us_per_round", "bfs_rounds_per_sync"):
        assert math.isfinite(metrics[name]["value"]), name
    assert metrics["bfs_rounds_per_sync"]["value"] > 1
    assert "bfs_dense_ns_per_slot" not in metrics
