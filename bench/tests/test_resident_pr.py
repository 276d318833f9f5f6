"""The ``g500-22.pr`` cell (PageRank on the resident graph) at scale 10 on
the CPU: the sound program is correct and compiles nothing inside its
window, a planted fault is not correct, and the control fails the
cell's check."""

import jax.numpy as jnp
import pytest

from bench import control, harness
from bench.tests import tiny
from repro.core.algorithms import pagerank as program_pagerank

CELL = "g500-22.pr"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tiny"))


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: None)


def test_the_sound_program_is_correct_and_compiles_nothing_in_the_window(
        root):
    result = harness.run_cell(root, CELL, 3, seconds=0.5, trace=False,
                              chips_check=tiny.cpu_devices)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 2
    assert result["window_compiles"] == 0
    assert result["metrics"]["pr_mevps"]["value"] > 0


def test_a_planted_fault_is_not_correct(root, monkeypatch):
    sound = program_pagerank.pr_pull

    def altered(g, damping, tol, max_iters):
        rank, stats = sound(g, damping=damping, tol=tol, max_iters=max_iters)
        return rank * jnp.float32(1.001), stats

    monkeypatch.setattr(program_pagerank, "pr_pull", altered)
    result = tiny.run(root, CELL)
    assert result["correct"] is False and result["failed"] >= 1


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_the_control_fails_the_check(root, seed):
    reading = control.control(root, CELL, seed)
    assert reading["fails"], reading
