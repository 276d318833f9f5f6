"""Correctness of the seven paper benchmarks against numpy oracles."""

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import from_coo
from repro.core.algorithms import bc, bfs, cc, kcore, pagerank, sssp, tc
from repro.graphs import generators as gen

import oracles

GRAPHS = {
    "rmat_small": lambda: gen.rmat(7, 8, seed=3),
    "web_like": lambda: gen.web_crawl_like(8, 4, 6, 2, seed=1),
    "erdos": lambda: gen.erdos(300, 2500, seed=2),
    "grid": lambda: gen.grid2d(17, 13),
    "path": lambda: gen.path(50),
}


def build(name, symmetrize=False, weighted=False, csc=False, block=64):
    src, dst, n = GRAPHS[name]()
    w = gen.random_weights(len(src), seed=7) if weighted else None
    g = from_coo(src, dst, n, w, block_size=block, build_csc=csc,
                 symmetrize=symmetrize)
    # matching host-side edge list (post symmetrize/dedup) for the oracle
    s = np.asarray(g.src_idx)[: g.m]
    d = np.asarray(g.col_idx)[: g.m]
    ww = np.asarray(g.edge_w)[: g.m]
    return g, s, d, ww, n


def max_outdeg_vertex(s, n):
    return int(np.argmax(np.bincount(s, minlength=n)))


@pytest.mark.parametrize("gname", list(GRAPHS))
@pytest.mark.parametrize("variant", ["topo", "dd_dense", "dd_sparse", "dirop"])
def test_bfs(gname, variant):
    g, s, d, _, n = build(gname, csc=(variant == "dirop"))
    source = max_outdeg_vertex(s, n)
    ref = oracles.bfs(s, d, n, source)
    dist, stats = bfs.VARIANTS[variant](g, source)
    dist = np.asarray(dist)[:n]
    got = np.where(dist > 1e30, np.inf, dist)
    np.testing.assert_allclose(got, ref, rtol=0, atol=0)
    assert stats.rounds > 0


@pytest.mark.parametrize("gname", ["rmat_small", "web_like", "grid"])
@pytest.mark.parametrize("variant", ["bellman_ford", "dd_dense", "dd_sparse", "delta"])
def test_sssp(gname, variant):
    g, s, d, w, n = build(gname, weighted=True)
    source = max_outdeg_vertex(s, n)
    ref = oracles.dijkstra(s, d, w, n, source)
    dist, _ = sssp.VARIANTS[variant](g, source)
    dist = np.asarray(dist)[:n]
    got = np.where(dist > 1e30, np.inf, dist)
    finite = np.isfinite(ref)
    assert np.array_equal(np.isfinite(got), finite)
    np.testing.assert_allclose(got[finite], ref[finite], rtol=1e-5)


@pytest.mark.parametrize("gname", ["rmat_small", "web_like", "erdos", "grid"])
@pytest.mark.parametrize(
    "variant", ["labelprop", "labelprop_sc", "pointer_jump", "dd_sparse"])
def test_cc(gname, variant):
    g, s, d, _, n = build(gname, symmetrize=True)
    ref = oracles.connected_components(s, d, n)
    lab, _ = cc.VARIANTS[variant](g)
    lab = np.asarray(lab)[:n]
    # same partition: labels must induce identical equivalence classes
    _, ref_ids = np.unique(ref, return_inverse=True)
    _, got_ids = np.unique(lab, return_inverse=True)
    assert np.array_equal(ref_ids, got_ids)


@pytest.mark.parametrize("gname", ["rmat_small", "web_like", "grid"])
@pytest.mark.parametrize("variant", ["pull", "push"])
def test_pagerank(gname, variant):
    # symmetrize → no dangling vertices → push and pull share a fixpoint
    g, s, d, _, n = build(gname, symmetrize=True, csc=True)
    ref = oracles.pagerank(s, d, n)
    if variant == "pull":
        rank, _ = pagerank.pr_pull(g, tol=1e-10, max_iters=300)
    else:
        rank, _ = pagerank.pr_push(g, tol=1e-12, max_iters=5000)
    rank = np.asarray(rank)[:n]
    np.testing.assert_allclose(rank, ref, rtol=2e-3, atol=1e-8)


def test_resident_pr_pull_compiles_once_for_any_iteration_count():
    """A resident run is one jitted program, shared by runs of any
    ``max_iters``: a benchmark's one-iteration warm-up compiles what its
    ten-iteration jobs run."""
    g, s, d, _, n = build("grid", symmetrize=True, csc=True)
    pagerank.pr_pull(g, tol=0.0, max_iters=1)
    programs = pagerank._pr_pull_resident._cache_size()
    rank3, st3 = pagerank.pr_pull(g, tol=0.0, max_iters=3)
    rank10, st10 = pagerank.pr_pull(g, tol=0.0, max_iters=10)
    assert pagerank._pr_pull_resident._cache_size() == programs
    assert (st3.rounds, st10.rounds) == (3, 10)
    assert st10.edges_touched == 10 * g.m
    assert not np.array_equal(np.asarray(rank3), np.asarray(rank10))


@pytest.mark.parametrize("gname", ["rmat_small", "web_like", "erdos"])
def test_bfs_dirop_forced_pull_directed(gname):
    """Direction-optimizing BFS with the switch heuristic skewed so the
    pull (CSC) path actually runs on DIRECTED, non-symmetrized graphs —
    with Beamer defaults these small graphs may never leave push, leaving
    pull_dense's asymmetric-CSC handling untested."""
    g, s, d, _, n = build(gname, csc=True)
    source = max_outdeg_vertex(s, n)
    ref = oracles.bfs(s, d, n, source)
    # alpha tiny -> switch to pull almost immediately; beta huge -> stay there
    dist, stats = bfs.bfs_dirop(g, source, alpha=0.01, beta=1e9)
    got = np.asarray(dist)[:n]
    got = np.where(got > 1e30, np.inf, got)
    np.testing.assert_allclose(got, ref, rtol=0, atol=0)
    assert stats.rounds > 0


def test_bfs_dirop_direction_sensitive_accounting():
    """Pin the dirop switch schedule and work ledger on a fixed asymmetric
    fan-out/fan-in graph (0 → 8 hubs → 20 shared leaves → 1 sink), replayed
    edge-for-edge in numpy.  The bugfix under test: a pull round charges
    the heuristic's ``visited_edges`` by the frontier's IN-degree mass and
    ``edges_touched`` by the bottom-up scan set (in-degree mass of
    still-unvisited vertices) — the old path charged out-degree mass and
    ``rounds·m`` regardless of direction, which on this graph reports 752
    instead of 576 and skews the α/β switch on asymmetric digraphs."""
    hubs = np.arange(1, 9)
    leaves = np.arange(9, 29)
    src = np.concatenate([np.zeros(8, np.int64), np.repeat(hubs, len(leaves)),
                          leaves])
    dst = np.concatenate([hubs, np.tile(leaves, len(hubs)),
                          np.full(len(leaves), 29, np.int64)])
    g = from_coo(src, dst, n=30, build_csc=True)
    alpha, beta = 2.0, 4.0
    dist, stats = bfs.bfs_dirop(g, 0, alpha=alpha, beta=beta)

    # numpy replay of the step, mirroring bfs_dirop exactly
    s = np.asarray(g.src_idx)[: g.m]
    d = np.asarray(g.col_idx)[: g.m]
    out_deg = np.asarray(g.out_deg)
    in_deg = np.zeros(g.n_pad, np.int64)
    np.add.at(in_deg, d, 1)
    INF = np.float32(np.finfo(np.float32).max)
    dr = np.full(g.n_pad, INF, np.float32)
    dr[0] = 0.0
    mask = np.zeros(g.n_pad, bool)
    mask[0] = True
    pull, ve, work, dirs = False, 0.0, 0, []
    while mask.any():
        fcount = mask.sum()
        out_mass = out_deg[mask].sum()
        in_mass = in_deg[mask].sum()
        go_pull = out_mass > max(g.m - ve, 0.0) / alpha
        go_push = fcount < g.n / beta
        pull = (not go_push) if pull else bool(go_pull)
        scan_mass = in_deg[dr == INF].sum()
        new = dr.copy()
        for u, v in zip(s, d):
            if mask[u]:
                new[v] = min(new[v], dr[u] + np.float32(1.0))
        upd = new != dr
        upd[-1] = False
        ve += in_mass if pull else out_mass
        work += scan_mass if pull else g.m
        dirs.append("pull" if pull else "push")
        dr, mask = new, upd

    assert np.array_equal(np.asarray(dist), dr)
    # hard literals: the switch schedule and both ledgers are load-bearing
    assert dirs == ["push", "pull", "pull", "push"]
    assert stats.rounds == len(dirs) == 4
    assert stats.pull_rounds == dirs.count("pull") == 2
    assert stats.edges_touched == work == 576  # old accounting: 4·188 = 752
    assert stats.edges_touched < stats.rounds * g.m


@pytest.mark.parametrize("gname", ["rmat_small", "web_like", "erdos"])
@pytest.mark.parametrize("substrate", ["jnp", "pallas"])
def test_pull_dense_directed_oracle(gname, substrate):
    """CSC pull on a directed, non-symmetrized graph against a direct numpy
    in-edge reduction (the parity suite only cross-checks substrates)."""
    from repro.core import operators as ops

    g, s, d, w, n = build(gname, weighted=True, csc=True)
    rng = np.random.default_rng(13)
    sv = np.rint(rng.normal(size=g.n_pad) * 3).astype(np.float32)
    active = rng.random(g.n_pad) < 0.6
    active[g.sentinel] = False
    init = np.full(g.n_pad, np.finfo(np.float32).max, np.float32)
    expect = init.copy()
    for u, v, ww in zip(s, d, w):  # in-edge u -> v relaxes v
        if active[u]:
            expect[v] = min(expect[v], np.float32(sv[u] + np.float32(ww)))
    got = ops.pull_dense(g, jnp.asarray(sv), jnp.asarray(active),
                         jnp.asarray(init), kind="min", substrate=substrate)
    np.testing.assert_array_equal(np.asarray(got), expect)


@pytest.mark.parametrize("gname", ["rmat_small", "web_like", "erdos"])
def test_pagerank_pull_directed_oracle(gname):
    """pr_pull on directed, non-symmetrized graphs: dangling-mass handling
    only shows up when out-degrees are asymmetric (the symmetrized cases in
    test_pagerank never exercise it)."""
    g, s, d, _, n = build(gname, csc=True)
    ref = oracles.pagerank(s, d, n)
    rank, _ = pagerank.pr_pull(g, tol=1e-10, max_iters=300)
    np.testing.assert_allclose(np.asarray(rank)[:n], ref, rtol=2e-3, atol=1e-8)


@pytest.mark.parametrize("gname", ["rmat_small", "erdos", "grid"])
@pytest.mark.parametrize("k", [2, 3, 5])
@pytest.mark.parametrize("variant", ["peel", "dd_sparse"])
def test_kcore(gname, k, variant):
    g, s, d, _, n = build(gname, symmetrize=True)
    ref = oracles.kcore_alive(s, d, n, k)
    alive, stats = kcore.VARIANTS[variant](g, k)
    assert np.array_equal(np.asarray(alive)[:n], ref)
    # work counter never exceeds the dense rounds x m cost
    assert stats.edges_touched <= stats.rounds * g.m


@pytest.mark.parametrize("gname", ["rmat_small", "web_like", "grid", "path"])
def test_bc(gname):
    g, s, d, _, n = build(gname)
    source = max_outdeg_vertex(s, n)
    ref = oracles.brandes_bc(s, d, n, source)
    score, _ = bc.bc_brandes(g, source)
    np.testing.assert_allclose(np.asarray(score)[:n], ref, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("gname", ["rmat_small", "web_like", "erdos", "grid"])
def test_tc(gname):
    g, s, d, _, n = build(gname, symmetrize=True)
    ref = oracles.triangle_count(s, d, n)
    got, _ = tc.tc_count(g, edge_chunk=4096)
    assert int(got) == ref
