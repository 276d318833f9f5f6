"""Property-based tests (hypothesis) for the engine's core invariants:
sparse advance ≡ dense push, compaction, capacity ladders, placement
interleaving, direction-optimizing switches."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

pytest.importorskip("hypothesis", reason="property tests need hypothesis "
                    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.core import from_coo
from repro.core import frontier as fr
from repro.core import operators as ops


def _graph(n, edges, seed):
    r = np.random.default_rng(seed)
    m = max(len(edges), 1)
    src = np.array([e[0] for e in edges], np.int64) if edges else np.array([0])
    dst = np.array([e[1] for e in edges], np.int64) if edges else np.array([1 % n])
    w = r.uniform(1, 4, len(src)).astype(np.float32)
    return from_coo(src % n, dst % n, n, w, block_size=16)


graph_strategy = st.builds(
    lambda n, edges, seed: (_graph(n, edges, seed), n),
    n=st.integers(4, 60),
    edges=st.lists(st.tuples(st.integers(0, 59), st.integers(0, 59)),
                   min_size=1, max_size=200),
    seed=st.integers(0, 2**31 - 1),
)


@settings(max_examples=25, deadline=None)
@given(gn=graph_strategy, mask_seed=st.integers(0, 2**31 - 1))
def test_sparse_advance_equals_dense_push(gn, mask_seed):
    """For ANY frontier, merge-path sparse relax == dense masked relax when
    the budget covers the frontier's edge mass."""
    g, n = gn
    r = np.random.default_rng(mask_seed)
    mask = jnp.asarray(r.random(g.n_pad) < 0.4)
    mask = mask.at[g.sentinel].set(False)
    mask = mask & (jnp.arange(g.n_pad) < g.n)
    vals = jnp.asarray(r.uniform(0, 10, g.n_pad).astype(np.float32))

    dense = ops.push_dense(g, vals, mask, vals, kind="min")

    cap = g.n_pad
    f = fr.compact(mask, cap, g.sentinel)
    budget = int(jnp.sum(jnp.where(mask, g.out_deg, 0))) + 16
    batch = ops.advance_sparse(g, f, budget)
    sparse = ops.relax_batch(batch, vals, vals, kind="min")
    np.testing.assert_allclose(np.asarray(dense), np.asarray(sparse))
    # advance enumerated exactly the frontier's edge mass
    assert int(batch.total) == int(jnp.sum(jnp.where(mask, g.out_deg, 0)))


@settings(max_examples=25, deadline=None)
@given(gn=graph_strategy, seed=st.integers(0, 2**31 - 1),
       cap_shift=st.integers(0, 3))
def test_compact_roundtrip(gn, seed, cap_shift):
    g, n = gn
    r = np.random.default_rng(seed)
    mask = jnp.asarray(r.random(g.n_pad) < 0.3)
    mask = mask.at[g.sentinel].set(False)
    true_count = int(jnp.sum(mask))
    cap = max(1, true_count << cap_shift)
    f = fr.compact(mask, cap, g.sentinel)
    assert int(f.count) == true_count
    idx = np.asarray(f.idx)
    got = set(idx[idx != g.sentinel][: true_count].tolist())
    expect = set(np.nonzero(np.asarray(mask))[0].tolist())
    assert got == expect


def test_capacity_ladder_monotone_covers():
    ladder = fr.ladder_capacities(4096, 64, base=4)
    assert ladder[-1] == 4096
    assert all(a < b for a, b in zip(ladder, ladder[1:]))
    for c in (1, 63, 64, 100, 4096):
        assert fr.pick_capacity(c, ladder) >= c


@settings(max_examples=20, deadline=None)
@given(nb=st.integers(1, 16), bs=st.sampled_from([4, 16]),
       ndev=st.sampled_from([1, 2, 4]))
def test_interleave_blocks_is_permutation(nb, bs, ndev):
    from repro.core.placement import interleave_blocks

    x = jnp.arange(nb * bs)
    y = interleave_blocks(x, bs, ndev)
    assert sorted(np.asarray(y).tolist()) == list(range(nb * bs))
    if nb % ndev == 0:
        # device d's contiguous shard holds blocks ≡ d (mod ndev)
        per = nb // ndev
        yv = np.asarray(y).reshape(nb, bs)
        for d in range(ndev):
            shard = yv[d * per:(d + 1) * per]
            blocks = set((shard[:, 0] // bs).tolist())
            assert all(b % ndev == d for b in blocks)


def test_direction_choice_hysteresis():
    g = _graph(32, [(0, 1)], 0)
    # big frontier mass → pull
    assert bool(ops.direction_choice(
        g, jnp.float32(1000.0), jnp.float32(100.0), jnp.float32(30.0),
        jnp.bool_(False)))
    # pull persists until the frontier shrinks below n/beta
    assert bool(ops.direction_choice(
        g, jnp.float32(10.0), jnp.float32(100.0), jnp.float32(30.0),
        jnp.bool_(True)))
    assert not bool(ops.direction_choice(
        g, jnp.float32(10.0), jnp.float32(100.0), jnp.float32(0.5),
        jnp.bool_(True)))


@settings(max_examples=15, deadline=None)
@given(gn=graph_strategy, src_seed=st.integers(0, 2**31 - 1))
def test_bfs_variants_agree(gn, src_seed):
    """All four BFS classes compute identical distances on arbitrary graphs
    (with unit weights)."""
    from repro.core.algorithms import bfs
    import dataclasses as dc

    g, n = gn
    g = dc.replace(g, edge_w=jnp.ones_like(g.edge_w))
    # need CSC for dirop — rebuild
    src = np.asarray(g.src_idx)[: g.m]
    dst = np.asarray(g.col_idx)[: g.m]
    g2 = from_coo(src, dst, n, block_size=16, build_csc=True)
    source = int(np.random.default_rng(src_seed).integers(0, n))
    outs = {}
    for name, fn in bfs.VARIANTS.items():
        d, _ = fn(g2, source)
        outs[name] = np.asarray(d)[:n]
    base = outs["topo"]
    for name, o in outs.items():
        np.testing.assert_allclose(o, base, err_msg=name)


# ---------------------------------------------------------------------------
# RunStats work accounting: edges_touched pinned against per-round oracles
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(gn=graph_strategy, k=st.integers(2, 5))
def test_kcore_edges_touched_is_removed_degree_mass(gn, k):
    """kcore_peel's edges_touched charges the removed-vertex degree mass —
    the per-round frontier out-degree sums, not rounds × m.  Each vertex is
    removed in exactly one round, so the oracle total is the static degree
    sum over everything the peel eventually removed."""
    from repro.core.algorithms import kcore

    g, n = gn
    # symmetrize the way kcore expects
    src = np.asarray(g.src_idx)[: g.m]
    dst = np.asarray(g.col_idx)[: g.m]
    gs = from_coo(src, dst, n, block_size=16, symmetrize=True)
    alive, stats = kcore.kcore_peel(gs, k)
    a = np.asarray(alive)
    removed = ~a & np.asarray(gs.valid_vertex_mask())
    expect = int(np.asarray(gs.out_deg)[removed].sum())
    assert stats.edges_touched == expect
    assert stats.edges_touched <= stats.rounds * gs.m


def test_kcore_sparse_tail_cheaper_than_dense_accounting():
    """On a path (the long-sparse-tail case) the ladder engine's work
    counter must stay near the tiny per-round frontier mass instead of
    paying m per round — the paper's work-efficiency claim for peeling."""
    from repro.core.algorithms import kcore
    from repro.graphs import generators as gen

    src, dst, n = gen.path(64)
    g = from_coo(src, dst, n, block_size=16, symmetrize=True)
    alive, stats = kcore.kcore_dd_sparse(g, 2)
    assert not bool(np.asarray(alive)[:n].any())  # paths have no 2-core
    assert stats.sparse_rounds > 0
    assert stats.edges_touched < stats.rounds * g.m
    # agreement with the dense peel, whose counter is the exact mass
    alive_d, stats_d = kcore.kcore_peel(g, 2)
    assert np.array_equal(np.asarray(alive), np.asarray(alive_d))
    assert stats_d.edges_touched == int(np.asarray(g.out_deg).sum())


@settings(max_examples=15, deadline=None)
@given(gn=graph_strategy, src_seed=st.integers(0, 2**31 - 1))
def test_bc_edges_touched_counts_fwd_and_bwd_sweeps(gn, src_seed):
    """bc's counter must reflect both sweeps: the forward level loop runs
    ecc+1 rounds of two full-edge relaxes (discovery min + sigma add), the
    backward loop ecc+1 rounds of one reversed relax — 3·(ecc+1)·m total,
    where ecc is the max finite BFS level from the source (oracle BFS)."""
    import oracles
    from repro.core.algorithms import bc

    g, n = gn  # bc is hop-count: the generator's random weights are ignored
    src = np.asarray(g.src_idx)[: g.m]
    dst = np.asarray(g.col_idx)[: g.m]
    source = int(np.random.default_rng(src_seed).integers(0, n))
    dist = oracles.bfs(src, dst, n, source)
    ecc = int(dist[np.isfinite(dist)].max())
    _, stats = bc.bc_brandes(g, source)
    fwd = ecc + 1  # the last forward round discovers nothing and stops
    assert stats.rounds == 2 * fwd
    assert stats.edges_touched == 3 * fwd * g.m
    assert stats.dense_rounds == 2 * fwd


# ---------------------------------------------------------------------------
# Device-resident rung execution: fused band-exit stretches must be
# indistinguishable from per-round dispatch (labels AND counters), with
# host syncs bounded by rung switches instead of rounds
# ---------------------------------------------------------------------------

_STAT_FIELDS = ("rounds", "edges_touched", "dense_rounds", "sparse_rounds",
                "overflow_escalations", "shard_escalations", "comm_elems",
                "comm_bytes", "reduce_axis_hops", "ndev", "placement",
                "substrate", "sparse_edges_touched")


def assert_stats_equal(st_fused, st_per_round, ctx=""):
    for f in _STAT_FIELDS:
        a, b = getattr(st_fused, f), getattr(st_per_round, f)
        assert a == b, (ctx, f, a, b)


@settings(max_examples=15, deadline=None)
@given(gn=graph_strategy, src_seed=st.integers(0, 2**31 - 1))
def test_fused_engine_equals_per_round_engine(gn, src_seed):
    """Property: for ANY graph and source, the fused engine's labels are
    bitwise identical to per-round dispatch and every RunStats counter
    (rounds, edges_touched, escalations, comm) is exactly equal — fusion
    only changes *when the host syncs*, never what executes."""
    from repro.core.algorithms import bfs, sssp

    g, n = gn
    source = int(np.random.default_rng(src_seed).integers(0, n))
    for name, fn in (("bfs", bfs.bfs_dd_sparse), ("sssp", sssp.sssp_dd_sparse)):
        lab_f, st_f = fn(g, source, fused=True)
        lab_p, st_p = fn(g, source, fused=False)
        got, want = np.asarray(lab_f), np.asarray(lab_p)
        assert got.dtype == want.dtype and np.array_equal(got, want), name
        assert_stats_equal(st_f, st_p, name)


def test_fused_engine_equals_per_round_kcore_mass_accounting():
    """kcore threads a labels *pytree* through the carry and charges dense
    fallback rounds the frontier degree mass (accumulated on device in the
    fused dense stretch) — both must match per-round dispatch exactly."""
    from repro.core.algorithms import kcore
    from repro.graphs import generators as gen

    src, dst, n = gen.web_crawl_like(12, 4, 8, 2, seed=5)
    g = from_coo(src, dst, n, block_size=64, symmetrize=True)
    for k in (2, 3, 4):
        alive_f, st_f = kcore.kcore_dd_sparse(g, k, fused=True)
        alive_p, st_p = kcore.kcore_dd_sparse(g, k, fused=False)
        assert np.array_equal(np.asarray(alive_f), np.asarray(alive_p)), k
        assert_stats_equal(st_f, st_p, f"kcore k={k}")
    assert st_f.dense_rounds + st_f.sparse_rounds == st_f.rounds
    # a cell whose peel crosses the dense cutoff, so the fused dense
    # stretch's on-device mass accumulator is genuinely compared (k = 7:
    # two dense rounds, then one sparse; since the top budget rung runs
    # dense, k = 8 peels dense throughout)
    src, dst, n = gen.web_crawl_like(10, 4, 9, 3, seed=0)
    g = from_coo(src, dst, n, block_size=16, symmetrize=True)
    alive_f, st_f = kcore.kcore_dd_sparse(g, 7, fused=True)
    alive_p, st_p = kcore.kcore_dd_sparse(g, 7, fused=False)
    assert st_f.dense_rounds > 0 and st_f.sparse_rounds > 0
    assert np.array_equal(np.asarray(alive_f), np.asarray(alive_p))
    assert_stats_equal(st_f, st_p, "kcore dense-mass cell")


def _count_blocking_fetches(monkeypatch):
    calls = {"n": 0}
    real = jax.device_get

    def counting(x):
        calls["n"] += 1
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting)
    return calls


def test_fused_host_syncs_scale_with_rung_switches(monkeypatch):
    """The band-exit contract: on a path graph (the paper's high-diameter
    regime — frontier size 1 for hundreds of rounds) the whole BFS is ONE
    rung stretch, so the fused run blocks on the device exactly twice
    (entry scalars + the stretch's single settle fetch) while per-round
    dispatch blocks once per round."""
    from repro.core.algorithms import bfs
    from repro.graphs import generators as gen

    src, dst, n = gen.path(256)
    g = from_coo(src, dst, n, block_size=16)
    calls = _count_blocking_fetches(monkeypatch)
    dist, st = bfs.bfs_dd_sparse(g, 0)
    assert st.rounds >= n - 2 and st.sparse_rounds == st.rounds
    assert calls["n"] <= 3, (st.rounds, calls["n"])
    fused_syncs = calls["n"]
    # contrast: per-round dispatch pays one scalar sync per round
    calls["n"] = 0
    dist_p, st_p = bfs.bfs_dd_sparse(g, 0, fused=False)
    assert calls["n"] >= st_p.rounds
    assert np.array_equal(np.asarray(dist), np.asarray(dist_p))
    assert fused_syncs < calls["n"] // 50


def test_fused_host_syncs_bounded_on_mixed_regime_run(monkeypatch):
    """A web-crawl-like sssp crosses rungs and the dense cutoff: syncs may
    grow with rung *switches* (each stretch = one fetch) but must stay
    far below the per-round count on any run with repeated same-rung
    rounds."""
    from repro.core.algorithms import sssp
    from repro.graphs import generators as gen

    src, dst, n = gen.web_crawl_like(24, 5, 10, 2, seed=2)
    w = gen.random_weights(len(src), seed=3)
    g = from_coo(src, dst, n, w, block_size=64)
    calls = _count_blocking_fetches(monkeypatch)
    _, st = sssp.sssp_dd_sparse(g, 0)
    # one fetch per stretch + the entry fetch; a regression to one-round
    # stretches (the pre-fusion model) would put stretches == rounds, so
    # demand genuine fusion: at most half as many stretches as rounds on
    # this seeded run (measured: 13 stretches over 42 rounds)
    stretches = calls["n"] - 1
    assert 1 <= stretches
    assert 2 * stretches <= st.rounds, (stretches, st.rounds)


@settings(max_examples=15, deadline=None)
@given(gn=graph_strategy, src_seed=st.integers(0, 2**31 - 1))
def test_sparse_engine_backend_invariant(gn, src_seed):
    """Property: end-to-end sparse-ladder BFS and SSSP results are bitwise
    identical on the jnp and Pallas substrates for arbitrary graphs and
    sources (min-reductions are order-independent, so any interleaving of
    blocked kernel scatters must agree exactly)."""
    from test_graph_ops_parity import check_backend_invariant

    g, n = gn
    source = int(np.random.default_rng(src_seed).integers(0, n))
    check_backend_invariant(g, source)
