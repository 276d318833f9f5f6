"""The sparse ladder's switch to the dense step (``frontier.dense_cutoff``).

The top budget rung spans the whole edge array, so a sparse round there
touches at least as many slots as the dense sweep and pays compaction and
the merge-path search besides.  The cutoff therefore sends every round
that would need the top rung to the dense step, and never sends a round
that the older "half the edge array" rule ran dense to the sparse path.
The labels and the fused / per-round counter equality stay as they were.
"""

import numpy as np
import pytest

import oracles
from repro.core import engine, from_coo
from repro.core import frontier as fr
from repro.core import multisource as ms
from repro.core.algorithms import bfs

# graph500-22: 128,303,242 arcs padded to 512-slot blocks
G500_22_M_PAD = 128_312_320


def _requests(ladder):
    """Every request size where the rung choice or the cutoff can change:
    each rung and its neighbours, the cutoff's and the half's."""
    top = ladder[-1]
    cut = fr.dense_cutoff(ladder)
    probe = {1, cut, cut + 1, top // 2, top // 2 + 1, top}
    for r in ladder:
        probe |= {r - 1, r, r + 1}
    return sorted(x for x in probe if 1 <= x <= top)


def test_dense_cutoff_on_graph500_22_ladder():
    ladder = fr.ladder_capacities(G500_22_M_PAD, 512)
    assert ladder[-2:] == (33_554_432, G500_22_M_PAD)
    assert fr.dense_cutoff(ladder) == 33_554_432
    # the frontier that ran sparse at the top rung before (mass in
    # (33,554,432, 64,156,160]) is now above the cutoff
    for mass in (33_554_433, 50_000_000, G500_22_M_PAD // 2, G500_22_M_PAD):
        assert mass > fr.dense_cutoff(ladder)
        assert fr.pick_capacity(mass, ladder) == ladder[-1]
    assert fr.pick_capacity(33_554_432, ladder) == 33_554_432


@pytest.mark.parametrize("top", [1, 17, 300, 512])
def test_dense_cutoff_one_rung_ladder_keeps_half(top):
    ladder = fr.ladder_capacities(top, 512)
    assert ladder == (top,)
    assert fr.dense_cutoff(ladder) == top // 2


@pytest.mark.parametrize("m_pad,block,base", [
    (G500_22_M_PAD, 512, 4),
    (912, 16, 4),     # rung below the top under the half
    (1100, 16, 4),    # rung below the top (1024) over the half (550)
    (4096, 64, 4),    # the top rung is itself a power of the base
    (17, 16, 4),      # two rungs, the lower one over the half
    (5000, 16, 2),
])
def test_dense_cutoff_sends_only_top_rung_and_old_dense_rounds_dense(
        m_pad, block, base):
    ladder = fr.ladder_capacities(m_pad, block, base)
    top, half = ladder[-1], ladder[-1] // 2
    cut = fr.dense_cutoff(ladder)
    assert len(ladder) > 1 and cut <= half
    if ladder[-2] <= half:
        assert cut == ladder[-2]
    for mass in _requests(ladder):
        rung = fr.pick_capacity(mass, ladder)
        if mass <= cut:
            # runs sparse, and never on the top rung
            assert rung < top, (mass, rung)
        else:
            # runs dense: it needed the top rung, or the older rule ran
            # it dense already
            assert rung == top or mass > half, (mass, rung)


def _top_rung_graph():
    """Directed two-level fan-out: 0 → 1..300, then each of those → two of
    301..900.  m = 900 pads to 912 (ladder 16, 64, 256, 912), so BFS from
    0 has a first round of mass 300: above the rung below the top (256),
    at or below half the edge array (456), on the top rung by
    ``pick_capacity``."""
    mid = np.arange(1, 301)
    src = np.concatenate([np.zeros(300, np.int64), mid, mid])
    dst = np.concatenate([mid, 301 + 2 * (mid - 1), 302 + 2 * (mid - 1)])
    n = 901
    return from_coo(src, dst, n, block_size=16), src, dst, n


def _assert_in_old_sparse_band(ladder, mass):
    assert fr.dense_cutoff(ladder) < mass <= ladder[-1] // 2
    assert fr.pick_capacity(mass, ladder) == ladder[-1]


def _hops(dist, n):
    d = np.asarray(dist)[:n]
    return np.where(d > 1e30, np.inf, d)


def test_top_rung_round_runs_dense_fused_and_per_round():
    g, src, dst, n = _top_rung_graph()
    runs = {}
    for fused in (True, False):
        eng = engine.SparseLadderEngine(g, bfs._sparse_step, bfs._dense_step,
                                        fused=fused)
        _assert_in_old_sparse_band(eng.budget_ladder, 300)
        top = eng.budget_ladder[-1]
        dist, _ = eng.run(bfs._init_dist(g, 0),
                          fr.dense_from_indices(np.array([0]), g.n_pad).mask)
        if fused:
            sparse_budgets = [k[2] for k in eng._stretch_keys
                              if k[0] == "sparse"]
        else:
            sparse_budgets = [budget for _, budget in eng._sparse]
        assert sparse_budgets and top not in sparse_budgets, sparse_budgets
        runs[fused] = (dist, eng.stats)
    (dist_f, st_f), (dist_p, st_p) = runs[True], runs[False]
    # rounds 1 (mass 300) and 2 (mass 600) dense, round 3 (mass 0) sparse
    assert (st_f.dense_rounds, st_f.sparse_rounds) == (2, 1)
    assert st_f.edges_touched == 2 * g.m + eng.budget_ladder[0]
    for f in ("rounds", "edges_touched", "dense_rounds", "sparse_rounds",
              "overflow_escalations", "shard_escalations",
              "sparse_edges_touched", "comm_elems"):
        assert getattr(st_f, f) == getattr(st_p, f), f
    dense, _ = bfs.bfs_dd_dense(g, 0)
    ref = oracles.bfs(src, dst, n, 0)
    for dist in (dist_f, dist_p):
        assert np.array_equal(np.asarray(dist), np.asarray(dense))
        np.testing.assert_array_equal(_hops(dist, n), ref)


def test_top_rung_round_runs_dense_multisource():
    g, src, dst, n = _top_rung_graph()
    sources = np.array([0, 1])
    eng = ms.MultiSourceEngine(g, ms._dist_sparse_step, ms._dist_dense_step)
    # the union's first round: 0's 300 arcs and 1's 2
    _assert_in_old_sparse_band(eng.budget_ladder, 302)
    dist0 = np.full((2, g.n_pad), ms.BFS_INF, np.float32)
    dist0[np.arange(2), sources] = 0.0
    dist, _ = eng.run(dist0, fr.batched_from_sources(sources, g.n_pad))
    budgets = [budget for _, budget in eng._sparse]
    assert budgets and eng.budget_ladder[-1] not in budgets, budgets
    assert (eng.stats.dense_rounds, eng.stats.sparse_rounds) == (2, 1)
    for lane, s in enumerate(sources):
        np.testing.assert_array_equal(_hops(dist[lane], n),
                                      oracles.bfs(src, dst, n, int(s)))
