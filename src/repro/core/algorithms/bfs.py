"""Breadth-first search — the paper's four implementation classes.

* ``bfs_topo``      topology-driven bulk-synchronous (Bellman-Ford-on-hops).
* ``bfs_dd_dense``  data-driven, dense bitmap worklist (Ligra/GBBS class).
* ``bfs_dd_sparse`` data-driven, sparse worklist via the capacity ladder
                    (Galois class — the paper's winner on high-diameter crawls).
* ``bfs_dirop``     direction-optimizing (Beamer) — wins on low-diameter
                    rmat/kron, loses on crawls (paper Fig. 6).

Distances are float32 (exact for any graph diameter we can hold).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import frontier as fr
from .. import operators as ops
from ..engine import (SparseLadderEngine, RunStats, run_dense,
                      run_streamed, _mask_cond, _mask_active)
from ..graph import Graph
from ...kernels.graph_ops.ref import scatter_at

INF = jnp.float32(jnp.finfo(jnp.float32).max)


def _init_dist(g: Graph, src: int):
    dist = g.vertex_full(INF, jnp.float32)
    return dist.at[src].set(0.0)


# Streamed (out-of-core) steps take the graph container as an argument —
# run_streamed hands them either the TieredGraph (eager rounds) or a
# StagedShards set (inside a fused stretch) — and live at module level so
# the jitted stretch's trace cache keys on stable identities.


def _topo_step(gr, state):
    dist, _ = state
    new = ops.push_dense(gr, dist, gr.valid_vertex_mask(), dist, kind="min",
                         use_weight=True)
    return new, jnp.any(new != dist)


def _topo_cond(state):
    return state[1]


def _topo_active(gr, state):
    return gr.valid_vertex_mask()


def _dd_step(gr, state):
    dist, mask = state
    new = ops.push_dense(gr, dist, mask, dist, kind="min", use_weight=True)
    return new, ops.updated_mask(dist, new)


def bfs_topo(g: Graph, src: int, max_rounds: int = 100_000):
    """Every round relaxes *all* edges (operator applied to every vertex)."""
    dist0 = _init_dist(g, src)
    state0 = (dist0, jnp.bool_(True))

    io0 = _io_snapshot(g)
    if getattr(g, "is_tiered", False):
        rounds, (dist, _) = run_streamed(
            g, _topo_step, state0, _topo_cond, _topo_active, max_rounds)
    else:
        # BFS relaxes hops: message is dist[src] + 1.  We reuse the
        # weighted relax with unit edge weights (builders set edge_w = 1
        # for unweighted graphs).
        rounds, (dist, _) = run_dense(
            lambda s: _topo_step(g, s), state0, _topo_cond, max_rounds)
    return dist, _dense_stats(g, rounds, io0)


def _io_snapshot(g):
    return g.io.snapshot() if getattr(g, "is_tiered", False) else None


def _dense_stats(g, rounds, io0=None) -> RunStats:
    """Stats for ``rounds`` dense rounds; on a tiered graph the edge and
    h2d accounting comes from the stream-counter delta since ``io0``
    instead of rounds·m."""
    rounds = int(rounds)
    stats = RunStats.from_graph(g, relaxes=rounds, rounds=rounds,
                                dense_rounds=rounds)
    if io0 is not None:
        g.io.fold_delta(stats, io0)
    else:
        stats.edges_touched = rounds * g.m
    return stats


def bfs_dd_dense(g: Graph, src: int, max_rounds: int = 100_000):
    """Data-driven: only vertices whose label changed last round push."""
    dist0 = _init_dist(g, src)
    mask0 = fr.dense_from_indices(jnp.array([src]), g.n_pad).mask

    io0 = _io_snapshot(g)
    if getattr(g, "is_tiered", False):
        rounds, (dist, _) = run_streamed(
            g, _dd_step, (dist0, mask0), _mask_cond, _mask_active,
            max_rounds)
    else:
        rounds, (dist, _) = run_dense(
            lambda s: _dd_step(g, s), (dist0, mask0), _mask_cond,
            max_rounds)
    return dist, _dense_stats(g, rounds, io0)


def _sparse_step(g, dist, mask, *, capacity: int, budget: int):
    new, esc = ops.sparse_round(g, dist, mask, dist, kind="min",
                                use_weight=True, capacity=capacity,
                                budget=budget)
    return new, ops.updated_mask(dist, new), esc


def _dense_step(g, dist, mask):
    new = ops.push_dense(g, dist, mask, dist, kind="min", use_weight=True)
    return new, ops.updated_mask(dist, new)


def bfs_dd_sparse(g: Graph, src: int, max_rounds: int = 100_000,
                  fused: bool = True, checkpointer=None):
    """Data-driven over the sparse-worklist ladder (the paper's Galois
    class).  ``fused`` selects device-resident rung stretches (default) vs
    one host dispatch per round — identical labels and RunStats either
    way.  ``checkpointer`` (a ``checkpoint.RunCheckpointer``) snapshots
    the (dist, frontier) state every K rounds and resumes an interrupted
    run bitwise (the labels are a pure function of the state at any
    round boundary)."""
    dist0 = _init_dist(g, src)
    mask0 = fr.dense_from_indices(jnp.array([src]), g.n_pad).mask
    eng = SparseLadderEngine(g, _sparse_step, _dense_step, fused=fused)
    dist, _ = eng.run(dist0, mask0, max_rounds, checkpointer=checkpointer)
    return dist, eng.stats


def bfs_incremental(g, dist, delta, max_rounds: int = 100_000,
                    fused: bool = True, checkpointer=None):
    """Re-converge BFS distances after a :class:`~..dynamic.DeltaBatch`.

    Inserts only shorten paths, so the converged ``dist`` stays a valid
    upper bound on the updated graph — the min-relax fixpoint is reached
    by seeding the ladder with just the batch's dirty sources (already
    reached ones; an unreached source has nothing to propagate) instead of
    restarting from the root.  The fixpoint is unique and every relax uses
    the same ``dist[src] + w`` message arithmetic, so the result is
    **bitwise** equal to a from-scratch ``bfs_dd_sparse`` on the updated
    container — the contract ``tests/test_dynamic*.py`` pin per batch and
    across compactions."""
    dirty = fr.dense_from_indices(
        jnp.asarray(delta.dirty.astype(jnp.int32)), g.n_pad).mask
    mask0 = dirty & (dist != INF)
    eng = SparseLadderEngine(g, _sparse_step, _dense_step, fused=fused)
    dist, _ = eng.run(dist, mask0, max_rounds, checkpointer=checkpointer)
    return dist, eng.stats


def _in_degrees(g) -> jax.Array:
    """(n_pad,) in-degree, from the CSC mirror.  Plain graphs carry it;
    sharded CSC mirrors don't, so count the flat in-edge destinations once
    (padding slots hit the sentinel, which is cleared)."""
    in_deg = getattr(g, "in_deg", None)
    if in_deg is not None:
        return in_deg
    idst = getattr(g, "in_dst", None)
    idst = g.in_src_idx if idst is None else idst.reshape(-1)
    counted = scatter_at(jnp.zeros((g.n_pad,), jnp.int32), idst,
                         jnp.ones(idst.shape, jnp.int32), "add")
    return counted.at[g.sentinel].set(0)


@partial(jax.jit, static_argnames=("n", "m", "alpha", "beta", "nshards"))
def _dirop_scalars(dist, mask, pull_prev, visited, out_deg, in_deg, owner,
                   *, n, m, alpha, beta, nshards):
    """Everything the streamed dirop's host loop needs for one round, in
    one fused device computation fetched in a single transfer:
    ``(frontier_count, pull?, direction_mass, scan_mass, live_shards)``.
    The α/β decision is computed ON DEVICE with the same f32 expressions
    as ``operators.direction_choice`` inside the resident trace, so the
    streamed run takes bitwise-identical direction switches."""
    fcount_i = jnp.sum(mask.astype(jnp.int32))
    fcount = fcount_i.astype(jnp.float32)
    out_mass = jnp.sum(jnp.where(mask, out_deg, 0)).astype(jnp.float32)
    in_mass = jnp.sum(jnp.where(mask, in_deg, 0)).astype(jnp.float32)
    unvisited = jnp.maximum(jnp.float32(m) - visited, 0.0)
    go_pull = out_mass > unvisited / alpha
    go_push = fcount < n / beta
    pull = jnp.where(pull_prev, ~go_push, go_pull)
    scan_mass = jnp.sum(jnp.where(dist == INF, in_deg, 0)).astype(jnp.int32)
    act = mask & (out_deg > 0)
    per = jnp.zeros((nshards,), jnp.int32).at[owner].add(act.astype(jnp.int32))
    return fcount_i, pull, jnp.where(pull, in_mass, out_mass), scan_mass, per > 0


def _bfs_dirop_streamed(g, src: int, max_rounds: int, alpha: float,
                        beta: float):
    """Direction-optimizing BFS out-of-core: push rounds stream the live
    CSR shards, pull rounds stream the whole CSC mirror (the bottom-up
    scan is dense by nature) — both through the same bounded pool.  One
    blocking fetch per round (``_dirop_scalars``) covers termination, the
    α/β switch, the frontier's direction mass, the pull round's in-degree
    scan mass, and the push schedule.  ``visited_edges`` accumulates on
    the host in float32, the same IEEE adds the resident while_loop
    carries, so direction switches — and with them labels and the PR 7
    accounting convention (push = m, pull = unvisited in-degree mass) —
    match the resident ``bfs_dirop`` exactly."""
    dist = _init_dist(g, src)
    mask = fr.dense_from_indices(jnp.array([src]), g.n_pad).mask
    io0 = g.io.snapshot()
    visited = np.float32(0.0)
    pull_prev = False
    work = pulls = rounds = 0
    while rounds < max_rounds:
        fcount, pull, mass_inc, scan_mass, live = jax.device_get(
            _dirop_scalars(dist, mask, jnp.bool_(pull_prev),
                           jnp.float32(visited), g.out_deg, g.in_deg,
                           g.owner, n=g.n, m=g.m, alpha=float(alpha),
                           beta=float(beta), nshards=g.nshards))
        if int(fcount) == 0:
            break
        pull = bool(pull)
        if pull:
            new = ops.pull_dense(g, dist, mask, dist, kind="min",
                                 use_weight=True)
            work += int(scan_mass)
        else:
            g.set_live_hint(np.asarray(live))
            new = ops.push_dense(g, dist, mask, dist, kind="min",
                                 use_weight=True)
            work += g.m
        dist, mask = new, ops.updated_mask(dist, new)
        visited = np.float32(visited + mass_inc)
        pull_prev = pull
        pulls += int(pull)
        rounds += 1
    stats = RunStats.from_graph(g, relaxes=rounds, rounds=rounds,
                                edges_touched=work, dense_rounds=rounds,
                                pull_rounds=pulls)
    # edges_touched follows Beamer's work convention here, not relaxed
    # edge slots — fold only the streaming/IO counters
    g.io.fold_delta(stats, io0, include_edges=False)
    return dist, stats


def bfs_dirop(
    g: Graph, src: int, max_rounds: int = 100_000, alpha: float = 14.0, beta: float = 24.0
):
    """Direction-optimizing BFS (needs CSC; doubles the graph footprint,
    exactly the memory cost the paper attributes to this class).

    Direction-sensitive accounting: a push round explores the frontier's
    *out*-edges, a pull round consumes the frontier's *in*-edges, so the
    heuristic's ``visited_edges`` accumulator charges each round by the
    mass of the direction it actually ran (charging out-degree mass on
    pull rounds skewed the α/β switch on asymmetric directed graphs).
    Work accounting follows Beamer's convention: a push round costs the
    full sweep (m — the dense push really processes every edge slot), a
    pull round costs the in-degree mass of the still-unvisited vertices
    (the bottom-up scan set), accumulated into ``edges_touched`` with the
    pull-round count in ``RunStats.pull_rounds``.
    """
    assert g.has_csc
    if getattr(g, "is_tiered", False):
        return _bfs_dirop_streamed(g, src, max_rounds, alpha, beta)
    dist0 = _init_dist(g, src)
    mask0 = fr.dense_from_indices(jnp.array([src]), g.n_pad).mask
    total_edges = jnp.float32(g.m)
    in_deg = _in_degrees(g)

    def step(state):
        dist, mask, pull, visited_edges, work, pulls = state
        fcount = jnp.sum(mask.astype(jnp.int32)).astype(jnp.float32)
        out_mass = jnp.sum(jnp.where(mask, g.out_deg, 0)).astype(jnp.float32)
        in_mass = jnp.sum(jnp.where(mask, in_deg, 0)).astype(jnp.float32)
        unvisited = jnp.maximum(total_edges - visited_edges, 0.0)
        pull = ops.direction_choice(g, out_mass, unvisited, fcount, pull,
                                    alpha, beta)
        # the bottom-up scan set: in-edges of vertices not yet reached
        scan_mass = jnp.sum(jnp.where(dist == INF, in_deg, 0)).astype(jnp.int32)

        def do_pull(_):
            return ops.pull_dense(g, dist, mask, dist, kind="min", use_weight=True)

        def do_push(_):
            return ops.push_dense(g, dist, mask, dist, kind="min", use_weight=True)

        new = jax.lax.cond(pull, do_pull, do_push, None)
        return (new, ops.updated_mask(dist, new), pull,
                visited_edges + jnp.where(pull, in_mass, out_mass),
                work + jnp.where(pull, scan_mass, jnp.int32(g.m)),
                pulls + pull.astype(jnp.int32))

    rounds, (dist, _, _, _, work, pulls) = run_dense(
        step,
        (dist0, mask0, jnp.bool_(False), jnp.float32(0.0), jnp.int32(0),
         jnp.int32(0)),
        lambda s: jnp.any(s[1]),
        max_rounds,
    )
    stats = RunStats.from_graph(g, relaxes=int(rounds), rounds=int(rounds),
                                edges_touched=int(work),
                                dense_rounds=int(rounds),
                                pull_rounds=int(pulls))
    return dist, stats


def bfs_batch(g: Graph, sources, max_rounds: int = 100_000):
    """Multi-source BFS: B concurrent sources share every edge sweep
    (``core/multisource.py``).  Row b is bitwise equal to
    ``bfs_dd_sparse(g, sources[b])``'s labels."""
    from .. import multisource as ms
    return ms.ms_distances(g, sources, INF, max_rounds)


VARIANTS = {
    "topo": bfs_topo,
    "dd_dense": bfs_dd_dense,
    "dd_sparse": bfs_dd_sparse,
    "dirop": bfs_dirop,
}
