"""Substrate parity: the Pallas graph_ops kernels vs the jnp reference.

Every relaxation operator (push / pull / advance+relax) must produce
**bitwise-identical** results on both substrates, for all four reduction
kinds, across ragged degree distributions (a hub with degree-1 leaves, an
empty frontier, ladder overflow → dense fallback).  Test data is
integer-valued so even the ``add`` reduction is exact in any summation
order; min/max/or are order-independent outright.

The end-to-end backend-invariance *property* test (random graphs via
hypothesis) lives in test_engine_properties.py and reuses
``check_backend_invariant`` from here.
"""

import os

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import from_coo
from repro.core import frontier as fr
from repro.core import operators as ops
from repro.core.algorithms import bfs, cc, pagerank, sssp
from repro.graphs import generators as gen

KINDS = ["min", "max", "add", "or"]


def hub_and_leaves(n_leaves=70):
    """Vertex 0 is a hub pointing at every leaf; leaves chain by degree 1 —
    the skew the merge-path budget assignment exists for."""
    src = [0] * n_leaves + list(range(1, n_leaves))
    dst = list(range(1, n_leaves + 1)) + list(range(2, n_leaves + 1))
    return np.array(src), np.array(dst), n_leaves + 1


GRAPHS = {
    "hub_leaves": hub_and_leaves,
    "web_like": lambda: gen.web_crawl_like(8, 4, 6, 2, seed=1),
    "erdos": lambda: gen.erdos(150, 1200, seed=2),
}


def build(name, block=64, csc=True):
    src, dst, n = GRAPHS[name]()
    rng = np.random.default_rng(5)
    w = rng.integers(1, 5, len(src)).astype(np.float32)  # integer-valued
    return from_coo(src, dst, n, w, block_size=block, build_csc=csc)


def vertex_data(g, kind, seed=0):
    """(src_val, active, out_init) triples; integer-valued floats so 'add'
    is exact in any order, bool for 'or'."""
    rng = np.random.default_rng(seed)
    active = jnp.asarray(rng.random(g.n_pad) < 0.5).at[g.sentinel].set(False)
    if kind == "or":
        sv = jnp.asarray(rng.random(g.n_pad) < 0.5)
        init = jnp.zeros((g.n_pad,), bool)
        return sv, active, init
    sv = jnp.asarray(np.rint(rng.normal(size=g.n_pad) * 3).astype(np.float32))
    fill = {"min": jnp.finfo(jnp.float32).max,
            "max": jnp.finfo(jnp.float32).min, "add": 0.0}[kind]
    return sv, active, g.vertex_full(fill, jnp.float32)


def assert_bitwise(a, b, what=""):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype, (what, a.dtype, b.dtype)
    np.testing.assert_array_equal(a, b, err_msg=what)


@pytest.mark.parametrize("reverse", [False, True])
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("gname", list(GRAPHS))
def test_push_parity(gname, kind, reverse):
    """Forward and reversed (bc's backward sweep) pushes: the reversed
    variant swaps gather/scatter roles but runs the same kernels."""
    g = build(gname)
    sv, active, init = vertex_data(g, kind)
    use_w = kind != "or"
    a = ops.push_dense(g, sv, active, init, kind=kind, use_weight=use_w,
                       substrate="jnp", reverse=reverse)
    b = ops.push_dense(g, sv, active, init, kind=kind, use_weight=use_w,
                       substrate="pallas", reverse=reverse)
    assert_bitwise(a, b, f"push/{gname}/{kind}/rev={reverse}")
    if reverse and kind == "min":
        # reversed push == forward push over the explicitly reversed graph
        src = np.asarray(g.src_idx)[: g.m]
        dst = np.asarray(g.col_idx)[: g.m]
        w = np.asarray(g.edge_w)[: g.m]
        gr = from_coo(dst, src, g.n, w, block_size=g.block_size, dedup=False)
        c = ops.push_dense(gr, sv, active, init, kind=kind,
                           use_weight=use_w, substrate="jnp")
        assert_bitwise(a, c, f"push-rev-vs-transpose/{gname}")


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("gname", list(GRAPHS))
def test_pull_parity(gname, kind):
    g = build(gname)
    sv, active, init = vertex_data(g, kind)
    use_w = kind != "or"
    a = ops.pull_dense(g, sv, active, init, kind=kind, use_weight=use_w,
                       substrate="jnp")
    b = ops.pull_dense(g, sv, active, init, kind=kind, use_weight=use_w,
                       substrate="pallas")
    assert_bitwise(a, b, f"pull/{gname}/{kind}")


@pytest.mark.parametrize("gname", list(GRAPHS))
def test_intersect_parity(gname):
    """tc's oriented-intersection op: both substrates share the binary
    search, so the per-chunk int32 counts must be bitwise equal — including
    on all-padding chunks."""
    from repro.core.algorithms import tc

    src, dst, n = GRAPHS[gname]()
    gs = from_coo(src, dst, n, block_size=64, symmetrize=True)
    adj, osrc, odst = tc.oriented_adjacency(gs)
    chunk = 64
    ne = int(osrc.shape[0])
    ne_pad = max((ne + chunk - 1) // chunk, 1) * chunk
    osrc = jnp.pad(osrc, (0, ne_pad - ne), constant_values=gs.sentinel)
    odst = jnp.pad(odst, (0, ne_pad - ne), constant_values=gs.sentinel)
    total_j = total_p = 0
    for c in range(0, ne_pad, chunk):
        a = ops.intersect_batch(adj, osrc[c:c + chunk], odst[c:c + chunk],
                                sentinel=gs.sentinel, substrate="jnp")
        b = ops.intersect_batch(adj, osrc[c:c + chunk], odst[c:c + chunk],
                                sentinel=gs.sentinel, substrate="pallas")
        assert int(a) == int(b), f"intersect/{gname}/chunk{c}"
        total_j += int(a)
        total_p += int(b)
    # padding-only chunk contributes exactly zero
    pad_s = jnp.full((chunk,), gs.sentinel, jnp.int32)
    z = ops.intersect_batch(adj, pad_s, pad_s, sentinel=gs.sentinel,
                            substrate="pallas")
    assert int(z) == 0
    count, _ = tc.tc_count(gs, edge_chunk=chunk)
    assert total_j == total_p == count


@pytest.mark.parametrize("frontier", ["some", "empty", "overflow"])
@pytest.mark.parametrize("gname", list(GRAPHS))
def test_advance_parity(gname, frontier):
    g = build(gname)
    rng = np.random.default_rng(3)
    if frontier == "empty":
        mask = jnp.zeros((g.n_pad,), bool)
    elif frontier == "overflow":
        mask = g.valid_vertex_mask()  # count >> capacity below
    else:
        mask = jnp.asarray(rng.random(g.n_pad) < 0.3)
    cap = g.block_size  # smallest rung: "overflow" genuinely overflows it
    f = fr.compact(mask, cap, g.sentinel)
    if frontier == "overflow":
        assert bool(f.overflowed())
    for budget in (g.block_size, 4 * g.block_size):
        a = ops.advance_sparse(g, f, budget, substrate="jnp")
        b = ops.advance_sparse(g, f, budget, substrate="pallas")
        for fld in ("src", "dst", "w", "valid", "total"):
            assert_bitwise(getattr(a, fld), getattr(b, fld),
                           f"advance/{gname}/{frontier}/{budget}/{fld}")
        if frontier == "empty":
            assert int(a.total) == 0 and not bool(jnp.any(a.valid))
        sv, _, init = vertex_data(g, "min")
        ra = ops.relax_batch(a, sv, init, kind="min", substrate="jnp")
        rb = ops.relax_batch(b, sv, init, kind="min", substrate="pallas")
        assert_bitwise(ra, rb, f"relax/{gname}/{frontier}/{budget}")


def run_both(fn):
    with ops.substrate_scope("jnp"):
        out_j, stats_j = fn()
    with ops.substrate_scope("pallas"):
        out_p, stats_p = fn()
    assert stats_j.substrate == "jnp" and stats_p.substrate == "pallas"
    return out_j, out_p


def check_backend_invariant(g, source):
    """End-to-end: sparse-ladder BFS and SSSP (incl. the overflow → dense
    fallback path) are bitwise backend-invariant.  Reused by the hypothesis
    property test in test_engine_properties.py."""
    d_j, d_p = run_both(lambda: bfs.bfs_dd_sparse(g, source))
    assert_bitwise(d_j, d_p, "bfs_dd_sparse")
    d_j, d_p = run_both(lambda: sssp.sssp_dd_sparse(g, source))
    assert_bitwise(d_j, d_p, "sssp_dd_sparse")
    return np.asarray(d_j)


@pytest.mark.parametrize("gname", list(GRAPHS))
def test_e2e_backend_invariant(gname):
    g = build(gname)
    check_backend_invariant(g, 0)


def test_e2e_dirop_and_cc_backend_invariant():
    src, dst, n = gen.web_crawl_like(8, 4, 6, 2, seed=4)
    g = from_coo(src, dst, n, block_size=64, build_csc=True, symmetrize=True)
    d_j, d_p = run_both(lambda: bfs.bfs_dirop(g, 0))
    assert_bitwise(d_j, d_p, "bfs_dirop")
    l_j, l_p = run_both(lambda: cc.cc_labelprop(g))
    assert_bitwise(l_j, l_p, "cc_labelprop")


@pytest.mark.parametrize("substrate", ["jnp", "pallas"])
def test_edgebatch_overflow_reporting(substrate):
    """When the budget cannot hold the frontier's edge mass, advance must
    still report the TRUE total (the engine's overflow check) and fill
    exactly ``budget`` valid slots — never silently under-report."""
    g = build("hub_leaves")  # hub vertex 0 has out-degree 70
    mask = jnp.zeros((g.n_pad,), bool).at[0].set(True)
    f = fr.compact(mask, g.block_size, g.sentinel)
    batch = ops.advance_sparse(g, f, budget=64, substrate=substrate)
    assert int(batch.total) == 70
    assert int(batch.total) > 64  # overflow correctly visible
    assert int(jnp.sum(batch.valid)) == 64
    # with a covering budget the same frontier enumerates everything
    batch2 = ops.advance_sparse(g, f, budget=128, substrate=substrate)
    assert int(batch2.total) == 70 and int(jnp.sum(batch2.valid)) == 70


def test_ladder_engine_escalates_instead_of_dropping(monkeypatch):
    """Force pick_capacity to hand the engine rungs that cannot hold the
    frontier: the engine must escalate those rounds to the dense step (and
    count them) rather than drop edges — labels stay bitwise identical."""
    from repro.core import engine as engine_mod
    from repro.core.algorithms.bfs import bfs_dd_sparse

    # hub round: edge mass 70 > the lowest budget rung 32, and at or under
    # the dense cutoff 80 (budget ladder 32, 128, 160), so it runs sparse;
    # at block 64 (ladder 64, 192) it would need the top rung and run dense
    g = build("hub_leaves", block=32, csc=False)
    ref, ref_stats = bfs_dd_sparse(g, 0)
    assert ref_stats.overflow_escalations == 0  # normal runs never overflow

    real_pick = fr.pick_capacity

    def lowball(count, ladder):
        return ladder[0]  # smallest rung regardless of demand

    monkeypatch.setattr(engine_mod.fr, "pick_capacity", lowball)
    got, stats = bfs_dd_sparse(g, 0)
    monkeypatch.setattr(engine_mod.fr, "pick_capacity", real_pick)
    assert stats.overflow_escalations > 0
    assert_bitwise(ref, got, "overflow escalation must not drop edges")


def float_vertex_data(g, seed=1):
    rng = np.random.default_rng(seed)
    sv = jnp.asarray(rng.normal(size=g.n_pad).astype(np.float32))
    active = jnp.asarray(rng.random(g.n_pad) < 0.7).at[g.sentinel].set(False)
    return sv, active, jnp.zeros((g.n_pad,), jnp.float32)


def build_float(name, block=64, csc=True):
    """Non-integer weights: summation ORDER is observable in the bits."""
    src, dst, n = GRAPHS[name]()
    rng = np.random.default_rng(8)
    w = rng.uniform(0.1, 2.0, len(src)).astype(np.float32)
    return from_coo(src, dst, n, w, block_size=block, build_csc=csc)


@pytest.mark.parametrize("op", ["push", "pull", "relax"])
def test_deterministic_add_bitwise_across_substrates(op):
    """The ROADMAP float-add item: under deterministic_add, kind='add'
    reduces in one fixed tree order on every substrate, so non-integer
    float sums match bitwise (plain mode only guarantees tolerance)."""
    g = build_float("web_like")
    sv, active, init = float_vertex_data(g)
    if op == "relax":
        f = fr.compact(active, g.n_pad, g.sentinel)
        batch = ops.advance_sparse(g, f, budget=4 * g.block_size,
                                   substrate="jnp")
        call = lambda sub: ops.relax_batch(batch, sv, init, kind="add",
                                           substrate=sub)
    elif op == "pull":
        call = lambda sub: ops.pull_dense(g, sv, active, init, kind="add",
                                          substrate=sub)
    else:
        call = lambda sub: ops.push_dense(g, sv, active, init, kind="add",
                                          substrate=sub)
    with ops.deterministic_add_scope():
        a = call("jnp")
        b = call("pallas")
    assert_bitwise(a, b, f"det-add/{op}")
    # the fixed-order sum is still the same sum, to float tolerance
    np.testing.assert_allclose(np.asarray(a), np.asarray(call("jnp")),
                               rtol=1e-5, atol=1e-6)


def test_pagerank_bitwise_across_substrates_with_det_add():
    """Pins the ROADMAP promise end-to-end: pagerank (float 'add' on
    non-integer contributions) becomes bitwise backend-reproducible under
    deterministic_add — compare with test_e2e_pagerank_close_across_backends,
    which can only assert allclose."""
    src, dst, n = gen.erdos(200, 1600, seed=6)
    g = from_coo(src, dst, n, block_size=64, build_csc=True)
    with ops.deterministic_add_scope():
        r_j, r_p = run_both(lambda: pagerank.pr_pull(g))
    assert_bitwise(r_j, r_p, "pagerank det-add")
    # deterministic mode changes the order, not the answer
    r_plain, _ = pagerank.pr_pull(g)
    np.testing.assert_allclose(np.asarray(r_j), np.asarray(r_plain),
                               rtol=1e-6, atol=1e-10)


def test_sharded_pagerank_bitwise_across_placement_and_ndev():
    """The cross-shard deterministic-add item: under deterministic_add the
    sharded float-add path re-orders the flat edge multiset into one
    canonical (src, dst, w) order before the fixed-order segmented tree,
    so pagerank is bitwise identical across every (placement × ndev) cell
    — AND to the unsharded deterministic result, because from_coo's CSR
    layout induces the same canonical order.  Runs in a subprocess with 8
    forced host devices (pattern of test_sharded_invariance.py)."""
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np
        import jax
        from jax.sharding import Mesh

        from repro.core import from_coo, shard_graph
        from repro.core import operators as ops
        from repro.core.algorithms import pagerank
        from repro.graphs import generators as gen

        src, dst, n = gen.erdos(120, 900, seed=6)
        g = from_coo(src, dst, n, block_size=16, build_csc=True)
        devs = np.array(jax.devices())

        with ops.deterministic_add_scope():
            ref, _ = pagerank.pr_pull(g)          # unsharded deterministic
            ref = np.asarray(ref)
            for ndev in (1, 2, 4, 8):
                mesh = Mesh(devs[:ndev], ("data",))
                for pol in ("local", "interleaved", "blocked"):
                    sg = shard_graph(g, mesh, ("data",), policy=pol)
                    got, st = pagerank.pr_pull(sg)
                    assert np.array_equal(ref, np.asarray(got)), (ndev, pol)
                    assert st.ndev == ndev
            # 2-D CVC cut reorders edges differently again — still bitwise
            mesh2 = Mesh(devs.reshape(4, 2), ("data", "model"))
            sg2 = shard_graph(g, mesh2, ("data", "model"), scheme="cvc",
                              grid=(4, 2))
            got2, _ = pagerank.pr_pull(sg2)
            assert np.array_equal(ref, np.asarray(got2))
        # plain (non-deterministic) sharded mode stays close, not bitwise
        plain, _ = pagerank.pr_pull(shard_graph(g, Mesh(devs, ("data",))))
        np.testing.assert_allclose(ref, np.asarray(plain), rtol=1e-6,
                                   atol=1e-10)
        print("SHARDED_DET_PAGERANK_OK")
        """
    )
    r = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
    )
    assert "SHARDED_DET_PAGERANK_OK" in r.stdout, r.stdout + r.stderr


def test_e2e_pagerank_close_across_backends():
    """pr_pull reduces with float 'add' on non-integer contributions, so the
    substrates may differ by summation order — allclose, not bitwise."""
    src, dst, n = gen.erdos(200, 1600, seed=6)
    g = from_coo(src, dst, n, block_size=64, build_csc=True)
    r_j, r_p = run_both(lambda: pagerank.pr_pull(g))
    np.testing.assert_allclose(np.asarray(r_j), np.asarray(r_p),
                               rtol=1e-6, atol=1e-9)


def test_engine_reuse_retraces_on_substrate_flip(monkeypatch):
    """A reused per-round SparseLadderEngine must drop step caches traced
    under the previous substrate — otherwise it executes one backend while
    reporting the other.  Counting actual kernel invocations matters: JAX
    shares trace caches across jit wrappers of the same function object, so
    a naive re-jit of the module-level step would NOT retrace and the
    pallas run would silently replay the jnp trace.  (The fused engine is
    immune by construction — the substrate is a *static jit argument* of
    its module-level stretch runners — see the test below.)"""
    from repro.core.engine import SparseLadderEngine
    from repro.core.algorithms.bfs import _dense_step, _init_dist, _sparse_step
    from repro.core import operators as ops_mod

    kernel_hits = []
    real_relax = ops_mod.gk.edge_relax

    def counting_relax(*a, **k):
        kernel_hits.append(1)
        return real_relax(*a, **k)

    monkeypatch.setattr(ops_mod.gk, "edge_relax", counting_relax)

    g = build("web_like")
    eng = SparseLadderEngine(g, _sparse_step, _dense_step, fused=False)
    mask0 = fr.dense_from_indices(jnp.array([0]), g.n_pad).mask
    with ops.substrate_scope("jnp"):
        d_j, _ = eng.run(_init_dist(g, 0), mask0)
        assert eng.stats.substrate == "jnp"
        compiles_first = eng.stats.compiles
    assert not kernel_hits  # jnp run must not touch the pallas kernels
    with ops.substrate_scope("pallas"):
        d_p, _ = eng.run(_init_dist(g, 0), mask0)
        assert eng.stats.substrate == "pallas"
        assert eng.stats.compiles > compiles_first  # caches were dropped
    assert kernel_hits, "pallas run never reached the pallas kernels"
    assert_bitwise(d_j, d_p, "engine reuse across substrates")


def test_fused_engine_substrate_is_static_trace_key(monkeypatch):
    """The fused engine's stretch runners are jitted at module level with
    the substrate as a static argument: a substrate flip on a reused
    engine keys a *different* trace, so the pallas run must actually reach
    the pallas kernels (at trace time) and report itself correctly.  The
    graph uses shapes unique to this test so the first pallas stretch
    cannot be satisfied by a trace cached from another test."""
    from repro.core.engine import SparseLadderEngine
    from repro.core.algorithms.bfs import _dense_step, _init_dist, _sparse_step
    from repro.core import operators as ops_mod

    kernel_hits = []
    real_relax = ops_mod.gk.edge_relax

    def counting_relax(*a, **k):
        kernel_hits.append(1)
        return real_relax(*a, **k)

    monkeypatch.setattr(ops_mod.gk, "edge_relax", counting_relax)

    src, dst, n = gen.web_crawl_like(7, 3, 5, 2, seed=23)
    g = from_coo(src, dst, n, block_size=23)  # unique n_pad/m_pad
    eng = SparseLadderEngine(g, _sparse_step, _dense_step)
    mask0 = fr.dense_from_indices(jnp.array([0]), g.n_pad).mask
    with ops.substrate_scope("jnp"):
        d_j, _ = eng.run(_init_dist(g, 0), mask0)
        assert eng.stats.substrate == "jnp"
    assert not kernel_hits  # jnp stretches must not touch pallas kernels
    with ops.substrate_scope("pallas"):
        d_p, _ = eng.run(_init_dist(g, 0), mask0)
        assert eng.stats.substrate == "pallas"
    assert kernel_hits, "pallas stretch never reached the pallas kernels"
    assert_bitwise(d_j, d_p, "fused engine reuse across substrates")


def test_substrate_selection_api():
    # the process default is env-selectable (CI runs the suite under both)
    assert ops.DEFAULT_SUBSTRATE == os.environ.get("REPRO_SUBSTRATE", "jnp")
    prev = ops.get_substrate()
    assert prev in ops.SUBSTRATES
    ops.set_substrate("pallas")
    try:
        assert ops.get_substrate() == "pallas"
    finally:
        ops.set_substrate(prev)
    with pytest.raises(ValueError):
        ops.set_substrate("cuda")
    with ops.substrate_scope("pallas"):
        assert ops.get_substrate() == "pallas"
    assert ops.get_substrate() == prev
    g = build("web_like")
    with pytest.raises(ValueError):
        sv, active, init = vertex_data(g, "min")
        ops.push_dense(g, sv, active, init, substrate="triton")


# ---------------------------------------------------------------------------
# Scatters split over operand ranges, and compaction (graph_ops.ref)
# ---------------------------------------------------------------------------
# On the chip a vertex array of graph500-22 size is scattered one operand
# range at a time; here ``SCATTER_CHUNK`` is cut so that small arrays take
# the same split path.

from repro.kernels.graph_ops import ref as gref  # noqa: E402


@pytest.mark.parametrize("chunk", [1 << 30, 64, 7])
@pytest.mark.parametrize("case", ["min", "max", "add", "or_bool", "add_int8",
                                  "set", "min_lanes"])
def test_scatter_at_ranges_match_one_scatter(monkeypatch, case, chunk):
    import jax
    monkeypatch.setattr(gref, "SCATTER_CHUNK", chunk)
    rng = np.random.default_rng(3)
    n, e = 301, 900
    idx = rng.integers(0, n, e).astype(np.int32)
    if case == "set":
        idx = rng.permutation(n)[:200].astype(np.int32)
        e = 200
    vals = rng.integers(-50, 50, e).astype(np.float32)
    if case == "or_bool":
        out, upd, op = np.zeros(n, bool), vals > 20, "max"
    elif case == "add_int8":
        out, upd, op = np.ones(n, np.int8), vals.astype(np.int8), "add"
    elif case == "min_lanes":
        out = rng.integers(0, 40, (3, n)).astype(np.float32)
        upd, op = rng.integers(-50, 50, (3, e)).astype(np.float32), "min"
    else:
        out, upd, op = np.full(n, 7.0, np.float32), vals, case
    f = jax.jit(lambda o, i, u: gref.scatter_at(o, i, u, op))
    got = np.asarray(f(jnp.asarray(out), jnp.asarray(idx), jnp.asarray(upd)))
    want = np.asarray(getattr(jnp.asarray(out).at[..., jnp.asarray(idx)],
                              op)(jnp.asarray(upd)))
    assert got.dtype == out.dtype
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("capacity", [1, 37, 300, 1111, 3000])
@pytest.mark.parametrize("density", [0.0, 0.1, 1.0])
def test_compact_indices_matches_nonzero(monkeypatch, capacity, density):
    monkeypatch.setattr(gref, "SCATTER_CHUNK", 256)
    rng = np.random.default_rng(capacity)
    mask = jnp.asarray(rng.random(2500) < density)
    got = gref.compact_indices(mask, capacity, 2499)
    (want,) = jnp.nonzero(mask, size=capacity, fill_value=2499)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n", [1, 1023, 1024, 5000])
def test_prefix_sum_matches_cumsum(n):
    x = jnp.asarray(np.random.default_rng(n).integers(0, 3, n), jnp.int32)
    np.testing.assert_array_equal(np.asarray(gref.prefix_sum(x)),
                                  np.cumsum(np.asarray(x)))


def test_engine_with_split_scatters_matches_oracle(monkeypatch):
    """BFS and SSSP through the engine with every vertex-array scatter and
    compaction split into ranges of a few hundred elements."""
    import jax
    from oracles import bfs as bfs_oracle, dijkstra
    monkeypatch.setattr(gref, "SCATTER_CHUNK", 333)
    jax.clear_caches()
    try:
        src, dst, n = gen.rmat(10, 8, seed=5)
        w = gen.random_weights(len(src), seed=6)
        g = from_coo(src, dst, n, w, build_csc=True)
        dist, _ = bfs.bfs_dd_sparse(from_coo(src, dst, n), 0)
        hops = np.asarray(dist)[:n]
        want = np.asarray(bfs_oracle(src, dst, n, 0), np.float64)
        np.testing.assert_array_equal(
            np.where(hops >= float(bfs.INF), np.inf, hops), want)
        sd, _ = sssp.sssp_delta(g, 0, delta=4.0)
        sd = np.asarray(sd, np.float64)[:n]
        ref = np.asarray(dijkstra(src, dst, w, n, 0), np.float64)
        np.testing.assert_allclose(
            np.where(sd >= float(sssp.INF), np.inf, sd), ref, rtol=1e-5)
    finally:
        jax.clear_caches()


@pytest.mark.parametrize("gather", [True, False])
@pytest.mark.parametrize("kind", ["min", "max", "add", "or"])
def test_sorted_segment_reduce_matches_segment_ops(kind, gather):
    """The scan-based reduction over destination-sorted edges against
    ``jax.ops`` segment reductions, with empty segments and runs that
    cross every power-of-two stride."""
    import jax
    rng = np.random.default_rng(11)
    n = 97
    deg = rng.integers(0, 9, n)
    deg[[3, 40, 96]] = 0
    deg[7] = 300
    row_ptr = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
    seg = np.repeat(np.arange(n, dtype=np.int32), deg)
    dtype = bool if kind == "or" else np.float32
    val = rng.integers(-20, 20, len(seg)).astype(dtype)
    red = "max" if kind == "or" else kind
    got = gref.sorted_segment_reduce(jnp.asarray(seg), jnp.asarray(val), red,
                                     n, jnp.asarray(row_ptr) if gather
                                     else None)
    segop = {"min": jax.ops.segment_min, "max": jax.ops.segment_max,
             "add": jax.ops.segment_sum}[kind if kind != "or" else "max"]
    want = np.asarray(segop(jnp.asarray(val), jnp.asarray(seg),
                            num_segments=n, indices_are_sorted=True))
    empty = deg == 0
    got = np.asarray(got)
    neutral = np.asarray(gref.neutral_for(red, val.dtype))
    np.testing.assert_array_equal(got[~empty], want[~empty])
    assert np.all(got[empty] == neutral)


@pytest.mark.parametrize("gname", list(GRAPHS))
@pytest.mark.parametrize("kind", KINDS)
def test_dense_push_over_sorted_edges_matches_scatter(gname, kind):
    """The jnp dense push reduces over the CSC mirror (and a reversed push
    over the CSR); on integer data it equals the scatter over the CSR
    bitwise."""
    g = build(gname)
    src_val, active, out_init = vertex_data(g, kind)
    for reverse in (False, True):
        s, d = (g.col_idx, g.src_idx) if reverse else (g.src_idx, g.col_idx)
        want = gref.push_ref(s, d, g.edge_w, src_val, active, out_init, kind)
        got = ops.push_dense(g, src_val, active, out_init, kind=kind,
                             substrate="jnp", reverse=reverse)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if kind in ("min", "max"):
        # a bool min is an AND, a bool max an OR
        bval, bact = src_val > 0, active
        binit = jnp.full((g.n_pad,), kind == "min")
        want = gref.push_ref(g.src_idx, g.col_idx, g.edge_w, bval, bact,
                             binit, kind, False)
        got = ops.push_dense(g, bval, bact, binit, kind=kind,
                             use_weight=False, substrate="jnp")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
