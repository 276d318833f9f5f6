"""PageRank — topology-driven pull vs data-driven residual push.

* ``pr_pull``  the standard power-iteration pull kernel every framework uses
               (paper: "all systems use the same algorithm for pr").  Needs
               CSC.  Dangling mass is redistributed uniformly.
* ``pr_push``  residual-based data-driven push (PR-Delta): only vertices with
               residual > tolerance push — the sparse-worklist formulation
               Galois can express.  Converges to the same fixpoint.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ...kernels import graph_ops as gk
from .. import operators as ops
from ..engine import RunStats, run_dense, run_host, run_streamed
from ..graph import Graph


class PRState(NamedTuple):
    """Un-normalised (rank, residual) pair carried between incremental
    solves — the push invariant ``resid = (1-d)·1 − rank + d·P rank``
    holds for it at every point, which is what lets a delta batch be
    absorbed as a residual correction instead of a recompute."""

    rank: jax.Array
    resid: jax.Array


def pr_pull(
    g: Graph,
    damping: float = 0.85,
    tol: float = 1e-6,
    max_iters: int = 100,
):
    """Power-iteration pull PageRank.  On a tiered (out-of-core) graph
    with a CSC mirror the rounds dispatch eagerly (``run_host``) — every
    iteration is a dense pull, streaming the whole in-edge cut through
    the buffer pool; float sums associate per shard, so results are
    allclose (not bitwise) to the resident run.  Otherwise the whole run
    is one jitted program, compiled once per graph shape, ``damping``,
    ``tol`` and operator mode: runs of any ``max_iters`` share it."""
    assert g.has_csc
    tiered = getattr(g, "is_tiered", False)
    if tiered:
        io0 = g.io.snapshot()
        step, state0 = _pull_program(g, damping)
        rounds, (rank, _) = run_host(step, state0, lambda s: s[1] > tol,
                                     max_iters)
    else:
        rounds, rank = _pr_pull_resident(
            g, jnp.int32(max_iters), damping=damping, tol=tol,
            sub=ops.get_substrate(), det=ops.get_deterministic_add())
    rounds = int(rounds)
    stats = RunStats.from_graph(g, relaxes=rounds, rounds=rounds,
                                edges_touched=0 if tiered else rounds * g.m,
                                dense_rounds=rounds)
    if tiered:
        g.io.fold_delta(stats, io0)
    return rank, stats


def _pull_program(g, damping: float):
    """``(step, state0)`` of ``pr_pull``'s power iteration on ``g``: state
    is ``(rank, resid)``, ``resid`` the L1 change of the last step."""
    n = jnp.float32(g.n)
    valid = g.valid_vertex_mask()
    outdeg = jnp.maximum(g.out_deg.astype(jnp.float32), 1.0)
    dangling = valid & (g.out_deg == 0)
    rank0 = jnp.where(valid, 1.0 / n, 0.0)

    def step(state):
        rank, _ = state
        contrib = jnp.where(valid, rank / outdeg, 0.0)
        pulled = ops.pull_dense(
            g, contrib, valid, jnp.zeros_like(rank), kind="add"
        )
        dmass = jnp.sum(jnp.where(dangling, rank, 0.0))
        new = jnp.where(valid, (1.0 - damping) / n + damping * (pulled + dmass / n), 0.0)
        resid = jnp.sum(jnp.abs(new - rank))
        return new, resid

    return step, (rank0, jnp.float32(jnp.inf))


@partial(jax.jit, static_argnames=("damping", "tol", "sub", "det"))
def _pr_pull_resident(g, max_iters, *, damping, tol, sub, det):
    with ops.substrate_scope(sub), ops.deterministic_add_scope(det):
        step, state0 = _pull_program(g, damping)
        rounds, (rank, _) = run_dense(step, state0, lambda s: s[1] > tol,
                                      max_iters)
    return rounds, rank


@lru_cache(maxsize=None)
def _pr_streamed_fns(damping: float, tol: float, absolute: bool = False):
    """(step, cond, active) triple for the streamed pr_push — cached per
    (damping, tol) so the jitted staged stretch's trace cache keys on
    stable function identities.  The step recomputes ``valid``/``outdeg``
    from the container it is handed (TieredGraph or StagedShards carry
    the same device arrays), so it traces cleanly inside the stretch.

    ``absolute=True`` gates activity on ``|resid| > tol`` — incremental
    warm starts carry *signed* residuals (an insert lowers 1/out_deg, so
    the correction subtracts mass along pre-existing edges) and negative
    residual must drain the same way positive residual spreads."""
    def gate(resid):
        return (jnp.abs(resid) if absolute else resid) > tol

    def step(gr, state):
        rank, resid = state
        outdeg = jnp.maximum(gr.out_deg.astype(jnp.float32), 1.0)
        active = gate(resid)
        rank = rank + jnp.where(active, resid, 0.0)
        push_val = jnp.where(active, damping * resid / outdeg, 0.0)
        added = ops.push_dense(
            gr, push_val, active, jnp.zeros_like(resid), kind="add",
            use_weight=False)
        resid = jnp.where(active, 0.0, resid) + added
        return rank, resid

    def cond(state):
        return jnp.any(gate(state[1]))

    def active_fn(gr, state):
        return gate(state[1])

    return step, cond, active_fn


def _pr_push_raw(g, damping, tol, max_iters, checkpointer=None, state0=None,
                 absolute=False):
    """Run the residual-push iteration to convergence from ``state0`` (or
    the cold uniform start) and return the raw ``(rank, resid, rounds)`` —
    no residual fold-in, no normalisation, so the result can seed a later
    warm solve.  Dispatch is the same as ``pr_push``: tiered containers go
    through ``run_streamed``, resident graphs through ``run_dense``."""
    if state0 is None:
        valid = g.valid_vertex_mask()
        rank0 = jnp.zeros((g.n_pad,), jnp.float32)
        resid0 = jnp.where(valid, 1.0 - damping, 0.0)
    else:
        rank0, resid0 = state0
    sstep, scond, sactive = _pr_streamed_fns(float(damping), float(tol),
                                             bool(absolute))
    if getattr(g, "is_tiered", False):
        rounds, (rank, resid) = run_streamed(
            g, sstep, (rank0, resid0), scond, sactive, max_iters,
            checkpointer=checkpointer)
    else:
        rounds, (rank, resid) = run_dense(
            lambda s: sstep(g, s), (rank0, resid0), scond, max_iters)
    return rank, resid, rounds


def pr_push(
    g: Graph,
    damping: float = 0.85,
    tol: float = 1e-9,
    max_iters: int = 10_000,
    checkpointer=None,
):
    """Residual push PageRank (un-normalised PPR-style formulation).

    rank converges to the solution of  r = (1-d)·1 + d·Aᵀ D⁻¹ r   (scaled by n
    vs the pull variant; we normalise at the end to match ``pr_pull``).

    ``checkpointer`` snapshots the (rank, residual) pair every K rounds on
    the tiered path and resumes an interrupted run — bitwise under
    ``operators.set_deterministic_add(True)`` (float add order is fixed),
    allclose otherwise.
    """
    # a tiered graph streams edge shards from host state, so rounds
    # dispatch through run_streamed: stable residual-active shard sets
    # fuse into device-resident stretches, the edge / h2d accounting comes
    # from the graph's stream counters instead of rounds·m, and the same
    # host boundaries carry the crash-recovery hooks (checkpointer; an
    # attached fault injector forces the per-round eager path)
    valid = g.valid_vertex_mask()
    tiered = getattr(g, "is_tiered", False)
    io0 = g.io.snapshot() if tiered else None
    rank, resid, rounds = _pr_push_raw(g, damping, tol, max_iters,
                                       checkpointer=checkpointer)
    rank = rank + resid  # fold in the leftover residual
    rank = jnp.where(valid, rank / jnp.sum(rank), 0.0)
    stats = RunStats.from_graph(
        g, relaxes=int(rounds), rounds=int(rounds),
        edges_touched=0 if tiered else int(rounds) * g.m,
        dense_rounds=int(rounds))
    if tiered:
        g.io.fold_delta(stats, io0)
    return rank, stats


def _delta_correction(g, delta, rank, resid, damping):
    """Fold an accepted edge batch into the push invariant.

    With od = max(out_deg, 1), the invariant maintained by every push round
    is  ``resid = (1-d)·1 − rank + d·Pᵀ rank``  where column v of P scales
    by 1/od[v].  Moving from graph G to G′ = G + delta changes P in exactly
    two ways: every pre-existing out-edge of a dirty source rescales from
    1/od_old to 1/od_new, and the delta edges appear with weight 1/od_new.
    Since delta sources gained exactly the delta edges:

        resid' = resid + d·[ push_{G'}(rank·(1/od_new − 1/od_old), dirty)
                             + Σ_{(u,v)∈delta} rank[u]/od_old[u] at v ]

    (the second term rewrites the delta edges' 1/od_new contribution plus
    the rescale double-count into the old-degree form; previously-dangling
    sources work out because od_old = 1 and their old column is empty).
    The first term relaxes through the container itself — the delta edges
    already sit in its logs — and the second is a fixed-order
    ``det_scatter_add`` over the batch, so the correction is deterministic
    whenever the container's adds are."""
    od_new = jnp.maximum(g.out_deg.astype(jnp.float32), 1.0)
    od_old = jnp.maximum(
        jnp.asarray(delta.old_out_deg).astype(jnp.float32), 1.0)
    dirty = jnp.zeros((g.n_pad,), bool)
    dirty = dirty.at[jnp.asarray(delta.dirty.astype(jnp.int32))].set(True)
    val = jnp.where(dirty, rank * (1.0 / od_new - 1.0 / od_old), 0.0)
    scaled = ops.push_dense(g, val, dirty, jnp.zeros_like(rank), kind="add",
                            use_weight=False)
    src = jnp.asarray(delta.src.astype(jnp.int32))
    dst = jnp.asarray(delta.dst.astype(jnp.int32))
    fresh = gk.det_scatter_add(dst, rank[src] / od_old[src],
                               jnp.zeros_like(rank))
    return resid + damping * (scaled + fresh)


def pr_incremental(
    g,
    delta=None,
    state: PRState | None = None,
    damping: float = 0.85,
    tol: float = 1e-9,
    max_iters: int = 10_000,
    checkpointer=None,
):
    """Incremental residual-push PageRank over a :class:`~..dynamic.DynamicGraph`.

    Cold call (``state=None``): a from-scratch ``pr_push`` solve that also
    returns its raw :class:`PRState`.  Warm call: the accepted
    ``DeltaBatch`` becomes a residual correction (``_delta_correction``)
    and the ladder re-converges from the dirty neighbourhood — only
    vertices whose residual the batch disturbed go active, so work scales
    with the perturbation, not with n.  The returned rank is normalised
    like ``pr_push``'s; the returned state is raw, to seed the next batch.

    Equality contract: allclose to a from-scratch ``pr_push`` on the
    updated container (the warm solve stops at a different residual
    profile below tol), and bitwise *reproducible* — same container, same
    batch history, any pool size / substrate / fused-vs-eager regime —
    under ``operators.set_deterministic_add(True)``."""
    valid = g.valid_vertex_mask()
    tiered = getattr(g, "is_tiered", False)
    io0 = g.io.snapshot() if tiered else None
    if state is None:
        rank, resid, rounds = _pr_push_raw(g, damping, tol, max_iters,
                                           checkpointer=checkpointer)
    else:
        rank0, resid0 = state.rank, state.resid
        if delta is not None and delta.inserted:
            resid0 = _delta_correction(g, delta, rank0, resid0, damping)
        rank, resid, rounds = _pr_push_raw(
            g, damping, tol, max_iters, checkpointer=checkpointer,
            state0=(rank0, resid0), absolute=True)
    out = rank + resid
    out = jnp.where(valid, out / jnp.sum(out), 0.0)
    stats = RunStats.from_graph(
        g, relaxes=int(rounds), rounds=int(rounds),
        edges_touched=0 if tiered else int(rounds) * g.m,
        dense_rounds=int(rounds))
    if tiered:
        g.io.fold_delta(stats, io0)
    return out, stats, PRState(rank=rank, resid=resid)


def ppr_push(
    g: Graph,
    src: int,
    damping: float = 0.85,
    tol: float = 1e-9,
    max_iters: int = 10_000,
):
    """Personalized PageRank by residual push from a single source: the
    same PR-Delta iteration as ``pr_push`` but with the whole unit of
    initial residual on ``src`` (Andersen-Chung-Lang push, normalized).
    This is the per-source reference the batched ``multisource.ms_ppr``
    lanes are checked against — op for op the same computation, so lanes
    match bitwise under ``operators.set_deterministic_add(True)``."""
    valid = g.valid_vertex_mask()
    outdeg = jnp.maximum(g.out_deg.astype(jnp.float32), 1.0)
    rank0 = jnp.zeros((g.n_pad,), jnp.float32)
    resid0 = rank0.at[src].set(1.0)

    def step(state):
        rank, resid = state
        active = resid > tol
        active = active.at[-1].set(False)
        rank = rank + jnp.where(active, resid, 0.0)
        push_val = jnp.where(active, damping * resid / outdeg, 0.0)
        added = ops.push_dense(
            g, push_val, active, jnp.zeros_like(resid), kind="add",
            use_weight=False
        )
        resid = jnp.where(active, 0.0, resid) + added
        return rank, resid

    rounds, (rank, resid) = run_dense(
        step, (rank0, resid0), lambda s: jnp.any(s[1] > tol), max_iters
    )
    rank = rank + resid
    rank = rank / jnp.sum(rank)
    rank = jnp.where(valid, rank, 0.0)
    return rank, RunStats.from_graph(
        g, relaxes=int(rounds), rounds=int(rounds),
        edges_touched=int(rounds) * g.m, dense_rounds=int(rounds))


def ppr_batch(g: Graph, sources, damping: float = 0.85, tol: float = 1e-9,
              max_rounds: int = 10_000):
    """Batched personalized PageRank over B concurrent sources
    (``core/multisource.py``): one fused edge sweep per round serves every
    lane.  Row b matches ``ppr_push(g, sources[b])`` (bitwise under
    deterministic add, allclose otherwise)."""
    from .. import multisource as ms
    return ms.ms_ppr(g, sources, damping, tol, max_rounds)


VARIANTS = {"pull": pr_pull, "push": pr_push}
