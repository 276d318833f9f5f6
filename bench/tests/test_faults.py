"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives the rest of a
run (generation, the program's container, warm-up, window, reference
check) at scale 10 on the CPU, with one fault planted in the program's
entry point: a step that hands back its state unchanged, or an answer
altered where it is produced.  The cells have no batch mean and no
exchange between chips, so those faults do not apply."""

import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests import tiny
from repro.core.algorithms import bfs as program_bfs
from repro.core.algorithms import pagerank as program_pagerank

BFS, PR = "g500-22.bfs", "g500-22-tiered.pr"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tiny"))


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    """The persistent cache is process-wide state; tests leave it as it
    is."""
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: None)


def _unchanged_bfs(g, src, *a, **k):
    _, stats = _sound_bfs(g, src, *a, **k)
    return program_bfs._init_dist(g, src), stats


def _altered_bfs(g, src, *a, **k):
    dist, stats = _sound_bfs(g, src, *a, **k)
    far = jnp.argmax(jnp.where(dist < program_bfs.INF, dist, -1.0))
    return dist.at[far].add(1.0), stats


def _unchanged_pagerank(g, damping, tol, max_iters):
    return _sound_pagerank(g, damping=damping, tol=tol, max_iters=0)


def _altered_pagerank(g, damping, tol, max_iters):
    rank, stats = _sound_pagerank(g, damping=damping, tol=tol,
                                  max_iters=max_iters)
    return rank.at[0].multiply(1.001), stats


_sound_bfs = program_bfs.bfs_dd_sparse
_sound_pagerank = program_pagerank.pr_pull


@pytest.mark.parametrize("cell", [BFS, PR])
def test_the_sound_program_is_correct(root, cell):
    result = tiny.run(root, cell)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 1


@pytest.mark.parametrize("cell, module, name, fault", [
    (BFS, program_bfs, "bfs_dd_sparse", _unchanged_bfs),
    (BFS, program_bfs, "bfs_dd_sparse", _altered_bfs),
    (PR, program_pagerank, "pr_pull", _unchanged_pagerank),
    (PR, program_pagerank, "pr_pull", _altered_pagerank),
], ids=["bfs-unchanged", "bfs-altered", "pr-unchanged", "pr-altered"])
def test_a_planted_fault_is_not_correct(root, monkeypatch, cell, module,
                                        name, fault):
    monkeypatch.setattr(module, name, fault)
    result = tiny.run(root, cell)
    assert result["correct"] is False
    assert result["failed"] >= 1
    (check,) = result["checks"].values()
    assert check["value"] > check["limit"]
