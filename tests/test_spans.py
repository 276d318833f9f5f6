"""Spans and timers inside the engine and the tiered store.

* Under ``jax.profiler`` tracing, every pool miss writes a ``tier.fetch``
  span (args ``sid``, ``direction``) on the interpreter's host line, with
  ``tier.read`` / ``tier.crc`` / ``tier.put`` inside it; fused engine
  stretches write ``engine.stretch`` and ``run_host`` rounds
  ``engine.round``.
* The fetch phase timers fit inside ``io_wait_us``; ``fetch_max_us`` is a
  run's own longest miss, and an injected read delay lands in both.
* The engine's regime timers and ``sparse_edges_touched`` are filled on
  the fused and the per-round path alike, and ``stretches`` counts the
  ``engine.stretch`` spans on both.
"""

import glob
import os
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import faultio, from_coo, tier_graph
from repro.core.algorithms import bfs, pagerank
from repro.core.faultio import FaultInjector
from repro.core.spans import Span
from repro.graphs import generators as gen

FETCH_PARTS = ("tier.read", "tier.crc", "tier.put")


def _tiered_pr_graph(nshards=4, resident=2):
    # scale 10, as the tiny benchmark cells
    src, dst, n = gen.rmat(10, 8, seed=5)
    g = from_coo(src, dst, n, symmetrize=True, build_csc=True)
    return tier_graph(g, nshards=nshards, resident_shards=resident,
                      build_csc=True)


def _bfs_graph(seed=2, n=600, m=4000):
    src, dst, n = gen.erdos(n, m, seed=seed)
    return from_coo(src, dst, n, symmetrize=True, block_size=32)


def _host_spans(log_dir):
    """``(name, start, end, args)`` of the program's spans on the
    interpreter's line of the host plane."""
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if line.name.startswith("python"):
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats)) for e in line.events
                           if e.name.startswith(("tier.", "engine.")))
    return out


def _traced(tmp_path, fn):
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    return out, _host_spans(str(tmp_path))


def test_span_times_itself_with_and_without_a_profiler():
    with Span("test.outer", k=1) as sp:
        pass
    assert sp.ns > 0 and sp.us == sp.ns // 1000
    opened = Span("test.open")
    assert opened.close() == opened.ns > 0


def test_tiered_pull_writes_fetch_spans_with_args_and_children(tmp_path):
    tg = _tiered_pr_graph()
    (rank, st), events = _traced(
        tmp_path, lambda: jax.block_until_ready(
            pagerank.pr_pull(tg, tol=0.0, max_iters=3)))
    fetches = [e for e in events if e[0] == "tier.fetch"]
    assert st.shards_streamed > 0
    assert len(fetches) == st.shards_streamed
    assert {f[3]["direction"] for f in fetches} == {"csc"}
    assert sorted({f[3]["sid"] for f in fetches}) == list(range(tg.nshards))
    for part in FETCH_PARTS:
        kids = [e for e in events if e[0] == part]
        assert len(kids) >= len(fetches), part
        for _, s, e, _ in kids:
            assert any(fs <= s and e <= fe for _, fs, fe, _ in fetches), part
    rounds = [e for e in events if e[0] == "engine.round"]
    assert [r[3]["round"] for r in rounds] == [0, 1, 2]


def test_fused_bfs_writes_stretch_spans(tmp_path):
    g = _bfs_graph()
    (dist, st), events = _traced(
        tmp_path, lambda: jax.block_until_ready(bfs.bfs_dd_sparse(g, 0)))
    stretches = [e for e in events if e[0] == "engine.stretch"]
    assert stretches
    assert {s[3]["regime"] for s in stretches} <= {"sparse", "dense"}
    for _, _, _, args in stretches:
        if args["regime"] == "sparse":
            assert args["capacity"] > 0 and args["budget"] > 0
    assert len(stretches) >= (st.sparse_rounds > 0) + (st.dense_rounds > 0)


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "per-round"])
def test_engine_regime_timers_and_sparse_slots(fused):
    g = _bfs_graph()
    t0 = time.perf_counter_ns()
    dist, st = bfs.bfs_dd_sparse(g, 0, fused=fused)
    dist.block_until_ready()
    wall_us = (time.perf_counter_ns() - t0) // 1000
    assert st.sparse_us + st.dense_us <= wall_us
    assert st.sparse_rounds > 0
    assert 0 < st.sparse_edges_touched <= st.edges_touched
    assert st.sparse_us > 0
    assert (st.dense_us > 0) == (st.dense_rounds > 0)
    if not st.dense_rounds:
        assert st.sparse_edges_touched == st.edges_touched


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "per-round"])
def test_stretches_count_the_stretch_spans(tmp_path, fused):
    """``RunStats.stretches`` is one per closed ``engine.stretch`` span:
    one per stretch fused, one per round (sparse or dense) per round."""
    g = _bfs_graph()
    (dist, st), events = _traced(
        tmp_path, lambda: jax.block_until_ready(
            bfs.bfs_dd_sparse(g, 0, fused=fused)))
    stretches = [e for e in events if e[0] == "engine.stretch"]
    assert st.stretches == len(stretches) > 0
    if fused:
        assert st.stretches <= st.rounds
    else:
        assert st.stretches == st.rounds
        assert st.dense_rounds > 0


def test_fetch_phase_timers_fit_io_wait():
    tg = _tiered_pr_graph()
    _, st = pagerank.pr_pull(tg, tol=0.0, max_iters=3)
    assert st.shards_streamed > 0 and st.io_wait_us > 0
    assert st.read_us + st.crc_us + st.put_us <= st.io_wait_us
    assert min(st.read_us, st.crc_us, st.put_us) >= 0 and st.crc_us > 0
    assert st.fetch_exposed_us <= st.io_wait_us
    assert st.fetch_max_us >= st.io_wait_us / st.shards_streamed
    assert st.fetch_max_us <= st.io_wait_us


def test_fetch_max_is_each_runs_own():
    """A slow run's longest miss does not carry into the next run, and a
    snapshot nested inside a run leaves the run's maximum whole."""
    tg = _tiered_pr_graph()
    tg.set_fault_injector(FaultInjector(
        [faultio.delay("shard_read", 0.05, key=tg.nshards + 1)]))
    _, slow = pagerank.pr_pull(tg, tol=0.0, max_iters=1)
    _, fast = pagerank.pr_pull(tg, tol=0.0, max_iters=1)
    assert slow.fetch_max_us >= 50_000
    assert fast.fetch_max_us < 50_000
    outer = tg.io.snapshot()
    _, inner = pagerank.pr_pull(tg, tol=0.0, max_iters=1)
    whole = type(inner)()
    tg.io.fold_delta(whole, outer)
    assert whole.fetch_max_us == inner.fetch_max_us > 0
    assert whole.io_wait_us == inner.io_wait_us


def test_injected_read_delay_lands_in_read_and_fetch_max():
    delay_s = 0.04
    tg = _tiered_pr_graph()
    tg.set_fault_injector(FaultInjector(
        [faultio.delay("shard_read", delay_s, key=tg.nshards + 2)]))
    _, st = pagerank.pr_pull(tg, tol=0.0, max_iters=2)
    assert st.read_us >= delay_s * 1e6
    assert st.fetch_max_us >= delay_s * 1e6
    assert st.read_us + st.crc_us + st.put_us <= st.io_wait_us


def test_exposed_fetches_are_those_begun_with_the_relax_drained():
    """Fetches issued once the relax's newest value is ready count as
    exposed; one issued behind an unfinished value does not."""

    class Pending:
        def is_ready(self):
            return False

    tg = _tiered_pr_graph()
    ready = jax.numpy.zeros(4)
    ready.block_until_ready()
    tg._fetch_behind(ready, 0, "csc")
    exposed = tg.io.fetch_exposed_us
    assert exposed == tg.io.io_wait_us > 0
    tg._fetch_behind(Pending(), 1, "csc")
    tg._fetch_behind(ready, 1, "csc")  # a pool hit: nothing to expose
    assert tg.io.fetch_exposed_us == exposed
    assert (tg.io.shards_streamed, tg.io.buffer_hits) == (2, 1)
    np.testing.assert_array_equal(
        np.asarray(tg._fetch_behind(ready, 0, "csc")[0]), tg._csc_host[0][0])
