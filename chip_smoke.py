#!/usr/bin/env python3
"""Chip smoke: the graph engine's main path, once, on one TPU chip.

The graph is LDBC Graphalytics' ``graph500-22``: a Graph500 Kronecker
graph at scale 22, edge factor 16 (A, B, C = 0.57, 0.19, 0.19), built
from ``--seed`` with ``graphs.generators.rmat`` and ``random_weights``.
Every phase runs through the library's own entry points on the default
substrate and checks its answer against a host reference that shares no
code with the engine (``scipy.sparse.csgraph``, or a numpy power
iteration for PageRank).

    python chip_smoke.py                # one chip: device, resident,
                                        # tiered, serve, dynamic
    python chip_smoke.py --four-chips   # the sharded engine on a 4-chip
                                        # mesh, against one device

Each phase prints one JSON line. Its ``compile_s`` / ``run_s`` are smoke
timings of one cold run, not benchmark numbers; ``slowest_compiles`` names
the programs that took longest to compile. Progress goes to standard
error, each line with the seconds since the start. The last line is
``{"ok": true, "device": {...}}``. A phase that fails raises, and the
script exits non-zero. Without a TPU the script exits non-zero before any
phase and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import scipy.sparse as sp  # noqa: E402
from scipy.sparse import csgraph  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import from_coo  # noqa: E402
from repro.core.algorithms import bfs, cc, pagerank, sssp  # noqa: E402
from repro.core.dynamic import dynamize  # noqa: E402
from repro.core.sharded import shard_graph  # noqa: E402
from repro.core.tiered import tier_graph  # noqa: E402
from repro.graphs import generators as gen  # noqa: E402
from repro.launch.graph_serve import GraphServer, QueryRequest  # noqa: E402

SCALE = 22          # graph500-22 (LDBC Graphalytics)
EDGE_FACTOR = 16
DAMPING = 0.85
PR_TOL = 1e-7       # pr_push's per-vertex residual threshold
SSSP_RTOL = 1e-5    # float32 path sums against float64 Dijkstra
SERVE_REQUESTS = 16
SERVE_BATCH = 8
ZIPF_A = 1.5        # source popularity skew of the served queries
INSERTS = 4096      # edges in the dynamic phase's insert batch
# Rounds of the streamed PageRank. Its live set is every shard, so with a
# pool of 4 of 16 shards each round re-reads (and re-checksums) the whole
# CSR, every shard padded to the largest (a third of the edges: rmat does
# not relabel vertices), about 23 s a round on a v5e host. It is compared
# with the resident push over the same CSR and rounds.
PR_STREAMED_ROUNDS = 4

# lowering and XLA compile; tracing is left out because nested jits report
# nested (overlapping) trace events
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class PhaseClock:
    """Wall time of a phase, split into compile (JAX's own lowering and
    backend-compile events) and the rest."""

    def __init__(self):
        self.compile_s = 0.0
        self.backend_compiles = []   # (seconds, program name)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, fun_name="?", **_):
        if name in _COMPILE_EVENTS:
            self.compile_s += secs
        if name == _COMPILE_EVENTS[-1]:
            self.backend_compiles.append((secs, fun_name))

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._on_event)

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = self.compile_s
        self.k0 = len(self.backend_compiles)
        return self

    def __exit__(self, *exc):
        self.wall = time.perf_counter() - self.t0
        self.compile = self.compile_s - self.c0
        self.programs = self.backend_compiles[self.k0:]
        return False

    def timing(self):
        """Compile and run seconds of the last ``with`` block, and how many
        programs it compiled (and how many of those in under a second)."""
        slowest = sorted(self.programs, reverse=True)[:3]
        return {"timing": "smoke: one cold run, not a benchmark",
                "compile_s": self.compile, "run_s": self.wall - self.compile,
                "programs": len(self.programs),
                "programs_under_1s": sum(s < 1.0 for s, _ in self.programs),
                "slowest_compiles": [[name, s] for s, name in slowest]}


_T0 = time.perf_counter()


def progress(what: str) -> None:
    print(f"chip_smoke: [{time.perf_counter() - _T0:8.1f} s] {what}",
          file=sys.stderr, flush=True)


def emit(record: dict) -> None:
    print(json.dumps(record, default=_jsonable), flush=True)


def _jsonable(x):
    if isinstance(x, np.generic):
        return x.item()
    raise TypeError(type(x).__name__)


def _stats(st) -> dict:
    keep = ("rounds", "edges_touched", "dense_rounds", "sparse_rounds",
            "compiles", "overflow_escalations", "shard_escalations",
            "h2d_bytes", "shards_streamed", "buffer_hits", "io_retries",
            "checksum_failures", "comm_elems", "sources", "ndev",
            "placement", "substrate")
    d = st.as_dict()
    return {k: d[k] for k in keep}


def _memory(device) -> dict:
    ms = device.memory_stats() or {}
    return {k: ms[k] for k in ("bytes_in_use", "peak_bytes_in_use")
            if k in ms}


# ---------------------------------------------------------------------------
# Graph and host references
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Workload:
    """The graph in its device containers plus the host reference matrices
    (built from the generator's COO, not from the engine's containers)."""

    n: int
    g: object          # weighted CSR + CSC (SSSP)
    gu: object         # same edges, unit weights (BFS, tiered, serve, dynamic)
    gsym: object       # symmetrized, unweighted (CC, PageRank), + CSC
    a: sp.csr_matrix   # weighted directed adjacency
    asym: sp.csr_matrix
    source: int
    build_s: float


def unit_weights(g):
    """``g`` with every real edge weighted 1 (padding stays 0): the
    unweighted graph on the same device CSR/CSC index arrays."""
    ones = (jnp.arange(g.m_pad) < g.m).astype(jnp.float32)
    kw = dict(edge_w=ones)
    if g.has_csc:
        kw["in_edge_w"] = ones
    return dataclasses.replace(g, **kw)


def csr_only(g):
    """``g`` without its CSC mirror: dense pushes then scatter over the
    CSR."""
    return dataclasses.replace(g, in_row_ptr=None, in_col_idx=None,
                               in_src_idx=None, in_edge_w=None, in_deg=None)


def build_workload(scale: int, seed: int, csc: bool = True) -> Workload:
    progress(f"building graph500-{scale}")
    t0 = time.perf_counter()
    src, dst, n = gen.rmat(scale, EDGE_FACTOR, seed)
    w = gen.random_weights(len(src), seed=seed + 1)
    g = from_coo(src, dst, n, w, build_csc=csc)
    gsym = from_coo(src, dst, n, symmetrize=True, build_csc=csc)
    jax.block_until_ready((g, gsym))
    build_s = time.perf_counter() - t0
    progress(f"graph on the device ({build_s:.1f} s); host references next")
    # rmat output is already free of self-loops and duplicates, so scipy's
    # duplicate summing cannot alter a weight here
    a = sp.csr_matrix((w.astype(np.float64), (src, dst)), shape=(n, n))
    pattern = sp.csr_matrix((np.ones(len(src)), (src, dst)), shape=(n, n))
    asym = ((pattern + pattern.T) > 0).astype(np.float64).tocsr()
    outdeg = np.diff(a.indptr)
    source = int(np.argmax(outdeg))
    return Workload(n=n, g=g, gu=unit_weights(g), gsym=gsym, a=a, asym=asym,
                    source=source, build_s=build_s)


def ref_hops(a, sources) -> np.ndarray:
    """(len(sources), n) hop distances, inf where unreached."""
    return csgraph.shortest_path(a, method="D", unweighted=True,
                                 indices=np.asarray(sources))


def ref_pagerank(asym, damping: float, tol: float) -> np.ndarray:
    """float64 power iteration with ``tests/oracles.pagerank``'s dangling
    rule (dangling mass spread uniformly)."""
    n = asym.shape[0]
    outdeg = np.diff(asym.indptr).astype(np.float64)
    at = asym.T.tocsr()
    dangling = outdeg == 0
    rank = np.full(n, 1.0 / n)
    for _ in range(10_000):
        contrib = np.where(dangling, 0.0, rank / np.maximum(outdeg, 1.0))
        new = (1 - damping) / n + damping * (at @ contrib
                                            + rank[dangling].sum() / n)
        delta = np.abs(new - rank).sum()
        rank = new
        if delta < tol:
            return rank
    raise RuntimeError("reference PageRank did not converge")


def pr_l1_bound(rounds: int) -> float:
    """Largest normalised L1 distance from the exact PageRank that
    ``pr_push`` may leave: the residuals it stops at, each below ``PR_TOL``,
    are worth at most 2·d/(1-d)·tol (the push invariant), plus one float32
    rounding step per round on every vertex's rank."""
    return 2 * DAMPING / (1 - DAMPING) * PR_TOL + rounds * 2.0 ** -23


def _pagerank_check(rank, rounds, asym) -> dict:
    bound = pr_l1_bound(rounds)
    ref = ref_pagerank(asym, DAMPING, tol=bound * 1e-3)
    got = np.asarray(rank, np.float64)[: asym.shape[0]]
    return {"l1_err": float(np.abs(got - ref).sum()), "l1_bound": bound}


def _hops_equal(dist, ref) -> bool:
    got = np.asarray(dist, np.float64)[: ref.shape[0]]
    got = np.where(got >= float(bfs.INF), np.inf, got)
    return bool(np.array_equal(got, ref))


def _same_partition(labels, ref_labels) -> bool:
    _, a = np.unique(np.asarray(labels)[: len(ref_labels)],
                     return_inverse=True)
    _, b = np.unique(ref_labels, return_inverse=True)
    return bool(np.array_equal(a, b))


def _require(record: dict, ok: bool, what: str) -> None:
    """Raise when a check failed, after printing the phase's record so far
    (with the failure) so that the numbers that failed are on record."""
    if not ok:
        emit(dict(record, failed=what))
        raise AssertionError(f"smoke check failed: {what}")


def _on_device(tree, device) -> bool:
    return all(leaf.devices() == {device}
               for leaf in jax.tree_util.tree_leaves(tree))


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def phase_device(devices) -> dict:
    d = devices[0]
    rec = {"phase": "device", "platform": d.platform,
           "kind": d.device_kind, "count": len(devices)}
    emit(rec)
    return rec


def phase_resident(wl: Workload, device, clock: PhaseClock) -> dict:
    s = wl.source
    out = {"phase": "resident", "n": wl.n, "m": wl.g.m, "m_sym": wl.gsym.m,
           "device_bytes": {"g": wl.g.csr_bytes * (2 if wl.g.has_csc else 1),
                            "gsym": wl.gsym.csr_bytes},
           "host_build_s": wl.build_s, "source": s}
    _require(out, _on_device((wl.g, wl.gu, wl.gsym), device),
             f"graph arrays not all on {device}")
    progress("resident: BFS")
    with clock:
        dist, st = bfs.bfs_dd_sparse(wl.gu, s)
        dist = jax.block_until_ready(dist)
    progress("resident: BFS reference")
    hops = ref_hops(wl.a, [s])[0]
    ok = _hops_equal(dist, hops)
    out["bfs"] = dict(clock.timing(), stats=_stats(st), exact=ok,
                      reached=int(np.isfinite(hops).sum()))
    _require(out, ok, "resident BFS hops differ from scipy")

    progress("resident: SSSP")
    with clock:
        sd, st = sssp.sssp_delta(wl.g, s, delta=4.0)
        sd = np.asarray(jax.block_until_ready(sd))[: wl.n]
    progress("resident: SSSP reference")
    ref = csgraph.dijkstra(wl.a, indices=s)
    got = np.where(sd >= float(sssp.INF), np.inf, sd.astype(np.float64))
    fin = np.isfinite(ref)
    same_reach = bool(np.array_equal(np.isfinite(got), fin))
    err = float(np.max(np.abs(got[fin] - ref[fin]) / ref[fin].clip(1.0)))
    out["sssp"] = dict(clock.timing(), stats=_stats(st),
                       same_reach=same_reach, max_rel_err=err,
                       rtol=SSSP_RTOL)
    _require(out, same_reach and err <= SSSP_RTOL,
             f"SSSP differs from float64 Dijkstra (max rel err {err})")

    progress("resident: CC")
    with clock:
        lab, st = cc.cc_pointer_jump(wl.gsym)
        lab = jax.block_until_ready(lab)
    ncomp, ref_lab = csgraph.connected_components(wl.asym, directed=False)
    ok = _same_partition(lab, ref_lab)
    out["cc"] = dict(clock.timing(), stats=_stats(st), same_partition=ok,
                     components=int(ncomp))
    _require(out, ok, "CC partition differs from scipy")

    progress("resident: PageRank")
    with clock:
        rank, st = pagerank.pr_push(wl.gsym, damping=DAMPING, tol=PR_TOL)
        rank = jax.block_until_ready(rank)
    progress("resident: PageRank reference")
    chk = _pagerank_check(rank, st.rounds, wl.asym)
    out["pagerank"] = dict(clock.timing(), stats=_stats(st), tol=PR_TOL,
                           **chk)
    _require(out, chk["l1_err"] <= chk["l1_bound"],
             f"PageRank differs from the reference: {chk}")
    out["memory"] = _memory(device)
    emit(out)
    return {"dist": dist}


def phase_tiered(wl: Workload, resident: dict, device, clock: PhaseClock,
                 nshards: int = 16, resident_shards: int = 4):
    """Stream the CSR through a pool of ``resident_shards`` device buffers
    and require the streamed answers to match the resident ones."""
    out = {"phase": "tiered", "n": wl.n, "nshards": nshards,
           "resident_shards": resident_shards}
    progress("tiered: BFS")
    tg = tier_graph(wl.gu, nshards=nshards, resident_shards=resident_shards)
    out["bfs_graph"] = {"csr_bytes": tg.csr_bytes,
                        "pool_bytes": tg.resident_budget,
                        "edge_bytes": wl.gu.m * 12}
    with clock:
        dist, st = bfs.bfs_dd_sparse(tg, wl.source)
        dist = jax.block_until_ready(dist)
    ok = bool(np.array_equal(np.asarray(dist), np.asarray(resident["dist"])))
    out["bfs"] = dict(clock.timing(), stats=_stats(st), bitwise=ok)
    _require(out, ok, "streamed BFS is not bitwise equal to the resident run")
    _require(out, st.h2d_bytes > 0 and st.shards_streamed > nshards,
             f"BFS did not stream (h2d={st.h2d_bytes}, "
             f"shards={st.shards_streamed})")

    progress("tiered: PageRank")
    tsym = tier_graph(wl.gsym, nshards=nshards,
                      resident_shards=resident_shards)
    with clock:
        rank, st = pagerank.pr_push(tsym, damping=DAMPING, tol=PR_TOL,
                                    max_iters=PR_STREAMED_ROUNDS)
        rank = np.asarray(jax.block_until_ready(rank))
    del tsym
    # the resident push over the CSR alone (no CSC mirror), as streamed:
    # with the mirror it would reduce over sorted in-edges in a tree, whose
    # float sums differ from a scatter's by more than association per shard
    want, _ = pagerank.pr_push(csr_only(wl.gsym), damping=DAMPING,
                               tol=PR_TOL, max_iters=PR_STREAMED_ROUNDS)
    want = np.asarray(want)
    diff = float(np.max(np.abs(rank - want)))
    # float adds associate per shard when streamed (tiered.py's contract)
    ok = bool(np.allclose(rank, want, rtol=1e-4, atol=1e-9))
    out["pagerank"] = dict(clock.timing(), stats=_stats(st), allclose=ok,
                           max_abs_diff=diff)
    _require(out, ok, f"streamed PageRank not allclose to resident ({diff})")
    _require(out, st.h2d_bytes > 0 and st.shards_streamed > nshards,
             f"PageRank did not stream (h2d={st.h2d_bytes}, "
             f"shards={st.shards_streamed})")
    out["memory"] = _memory(device)
    emit(out)
    return tg


def zipf_sources(wl: Workload, count: int, seed: int) -> np.ndarray:
    """``count`` query sources whose popularity follows a Zipf law over a
    seeded ranking of the vertices that have out-edges."""
    rng = np.random.default_rng(seed)
    cand = np.flatnonzero(np.diff(wl.a.indptr) > 0)
    ranked = rng.permutation(cand)
    r = np.minimum(rng.zipf(ZIPF_A, size=count) - 1, len(ranked) - 1)
    return ranked[r]


def phase_serve(wl: Workload, device, clock: PhaseClock, seed: int) -> dict:
    sources = zipf_sources(wl, SERVE_REQUESTS, seed + 3)
    progress("serve")
    server = GraphServer(wl.gu, algo="bfs", max_batch=SERVE_BATCH)
    reqs = [QueryRequest(rid=i, source=int(s), arrive_round=i // 4)
            for i, s in enumerate(sources)]
    with clock:
        done = server.serve(reqs)
    progress("serve: references")
    uniq = np.unique(sources)
    ref = dict(zip(uniq.tolist(), ref_hops(wl.a, uniq)))
    exact = [r.reject_reason is None and _hops_equal(r.labels, ref[r.source])
             for r in done]
    out = {"phase": "serve", "n": wl.n, "requests": len(done),
           "distinct_sources": len(uniq), "max_batch": SERVE_BATCH,
           **clock.timing(), "ticks": server.tick_no,
           "stats": _stats(server.eng.stats), "exact": sum(exact),
           "memory": _memory(device)}
    _require(out, all(exact),
             f"{len(exact) - sum(exact)} served answers differ from scipy")
    emit(out)
    return out


def phase_dynamic(wl: Workload, tg, resident: dict, device,
                  clock: PhaseClock, seed: int) -> dict:
    """One seeded insert batch; the incremental BFS must equal a
    from-scratch BFS on the updated graph bitwise, and scipy's BFS."""
    progress("dynamic")
    dyn = dynamize(tg)
    rng = np.random.default_rng(seed + 4)
    bsrc = rng.integers(0, wl.n, INSERTS)
    bdst = rng.integers(0, wl.n, INSERTS)
    delta = dyn.apply_batch(bsrc, bdst)
    with clock:
        inc, st_inc = bfs.bfs_incremental(dyn, resident["dist"], delta)
        inc = jax.block_until_ready(inc)
    t_inc = clock.timing()
    with clock:
        scratch, st_scr = bfs.bfs_dd_sparse(dyn, wl.source)
        scratch = jax.block_until_ready(scratch)
    bitwise = bool(np.array_equal(np.asarray(inc), np.asarray(scratch)))
    progress("dynamic: reference")
    upd = wl.a + sp.csr_matrix(
        (np.ones(len(delta.src)), (delta.src, delta.dst)), shape=wl.a.shape)
    exact = _hops_equal(inc, ref_hops(upd, [wl.source])[0])
    out = {"phase": "dynamic", "n": wl.n, "inserted": delta.inserted,
           "requested": INSERTS,
           "incremental": dict(t_inc, stats=_stats(st_inc)),
           "scratch": dict(clock.timing(), stats=_stats(st_scr)),
           "bitwise": bitwise, "exact": exact, "memory": _memory(device)}
    _require(out, bitwise, "incremental BFS differs from a from-scratch run")
    _require(out, exact, "incremental BFS differs from scipy's")
    emit(out)
    return out


def phase_four_chips(wl: Workload, devices, clock: PhaseClock) -> dict:
    """The sharded engine (1-D cut, owner-targeted CVC reducer) on a
    4-device mesh, against scipy and against one device."""
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(devices[:4]), ("data",))
    progress("four chips: sharding")
    sg = shard_graph(wl.gu, mesh)
    ssym = shard_graph(wl.gsym, mesh)
    homes = sorted({sh.device.id for sh in sg.src.addressable_shards})
    out = {"phase": "four_chips", "n": wl.n, "m": wl.gu.m,
           "ndev": sg.ndev, "epd": sg.epd, "reducer": sg.red.mode,
           "edge_shard_devices": homes}
    _require(out, len(homes) == 4, f"edge shards sit on devices {homes}")
    progress("four chips: BFS")
    with clock:
        dist4, st = bfs.bfs_dd_sparse(sg, wl.source)
        dist4 = jax.block_until_ready(dist4)
    t4 = clock.timing()
    with clock:
        dist1, st1 = bfs.bfs_dd_sparse(wl.gu, wl.source)
        dist1 = jax.block_until_ready(dist1)
    bitwise = bool(np.array_equal(np.asarray(dist4), np.asarray(dist1)))
    exact = _hops_equal(dist4, ref_hops(wl.a, [wl.source])[0])
    out["bfs"] = dict(t4, stats=_stats(st), bitwise_vs_one_device=bitwise,
                      exact=exact, one_device=clock.timing())
    _require(out, bitwise, "4-device BFS is not bitwise equal to one device")
    _require(out, exact, "4-device BFS differs from scipy")
    progress("four chips: PageRank")
    with clock:
        rank, st = pagerank.pr_push(ssym, damping=DAMPING, tol=PR_TOL)
        rank = jax.block_until_ready(rank)
    chk = _pagerank_check(rank, st.rounds, wl.asym)
    out["pagerank"] = dict(clock.timing(), stats=_stats(st), **chk)
    out["memory"] = [_memory(d) for d in devices[:4]]
    _require(out, chk["l1_err"] <= chk["l1_bound"],
             f"4-device PageRank differs from the reference: {chk}")
    emit(out)
    return out


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def run(devices, *, scale: int, seed: int, four_chips: bool,
        nshards: int = 16, resident_shards: int = 4) -> None:
    """Every phase of the selected path on ``devices`` (the caller has
    checked what they are)."""
    clock = PhaseClock()
    try:
        phase_device(devices)
        if four_chips:
            wl = build_workload(scale, seed, csc=False)
            phase_four_chips(wl, devices, clock)
            return
        wl = build_workload(scale, seed)
        resident = phase_resident(wl, devices[0], clock)
        tg = phase_tiered(wl, resident, devices[0], clock, nshards,
                          resident_shards)
        phase_serve(wl, devices[0], clock, seed)
        phase_dynamic(wl, tg, resident, devices[0], clock, seed)
    finally:
        clock.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded engine on a 4-chip mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = jax.devices()
    want = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < want:
        print(f"chip_smoke: needs {want} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    run(devices, scale=SCALE, seed=args.seed, four_chips=args.four_chips)
    d = devices[0]
    emit({"ok": True, "device": {"platform": d.platform,
                                 "kind": d.device_kind,
                                 "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
