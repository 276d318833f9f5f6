"""The controls come out not correct under the cells' own checks, at a
size a CPU test can hold (the same files, graphs cut to scale 10)."""

import pytest

from bench import control
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("cell", ["g500-22.bfs", "g500-22-tiered.pr"])
@pytest.mark.parametrize("seed", [1, 2**31 + 5, 77])
def test_the_control_fails_the_check(root, cell, seed):
    reading = control.control(root, cell, seed)
    assert reading["fails"], reading
    assert reading["value"] > reading["limit"]
