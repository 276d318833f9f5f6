"""The peaks table and the least bytes of a PageRank pull iteration."""

import numpy as np
import pytest

from bench import peaks, reference


def test_v5e_peaks():
    p = peaks.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flop_per_s"] == 197e12
    assert p["hbm_bytes"] == 16e9
    assert "TPU v5e" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks(kind)


def test_pull_bytes_of_a_hand_counted_graph():
    """A triangle 0-1-2 plus the edge 2-3, given with a duplicate, a
    reversed duplicate and a self-loop: 4 undirected edges, so 8 arcs,
    over 5 vertices (vertex 4 is isolated).  8 arcs x (4-byte index +
    4-byte gathered value) + 5 vertices x (row pointer, degree, rank in,
    rank out) x 4 bytes = 64 + 80."""
    src = np.array([0, 1, 2, 2, 0, 1, 3], np.int32)
    dst = np.array([1, 2, 0, 3, 1, 0, 3], np.int32)
    adj = reference.Adjacency(src, dst, 5)
    assert adj.arcs == 8 and adj.edges == 4
    assert adj.vertices_with_edge == 4
    assert peaks.pull_iteration_bytes(adj.arcs, adj.n) == 144
