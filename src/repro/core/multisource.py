"""Multi-source batched traversal — MS-BFS-style lane batching for serving.

The paper's premise is that expensive memory traffic must be amortized:
few big fetches instead of many small ones (P1/P2).  A serving tier gets
the same economics from *batching*: B concurrent queries (BFS / SSSP / PPR
sources) on one resident graph share every edge sweep.  The frontier
becomes a (B, n_pad) bool **bit-matrix** — row b is lane b's dense
frontier — and ONE fused relax per round expands it through the operator
seam (``operators.batched_push_dense`` / ``batched_relax_batch``), so each
edge is touched once per round instead of B times.  This is the MS-BFS
construction (Then et al.) with the lane axis playing the bit-field role:
on a vector unit the (B,) lane column is the machine word.

Work accounting is the serving story: ``RunStats.edges_touched`` charges
each round's sweep ONCE (the budget for a sparse union round, m for a
dense one) while ``RunStats.sources`` records B — so
``edges_touched / sources`` is the amortized per-source cost that
``benchmarks/serving.py`` reports and ``ci_gate.py serve`` gates against
the sequential per-source cost.

Execution structure:

* **Rounds** are dispatched one per host trip by :class:`MultiSourceEngine`
  — the per-round sibling of ``engine.SparseLadderEngine``.  The ladder
  keys on the **union** frontier row (``frontier.batched_round_scalars``
  returns ``(total, ucount, umass, alive)`` in one fetch): a sparse round
  compacts the union once, advances it once (merge-path), and relaxes the
  batch with per-lane slot masks; a dense round is one batched push.
  Per-round dispatch is deliberate — the serving scheduler
  (``launch/graph_serve.py``) admits and retires lanes *between* rounds,
  which a fused device-resident stretch cannot observe (the zero-sync
  follow-up in ROADMAP covers fusing stretches of a stable lane set).
* **Termination** is per lane: ``alive`` is the row-wise any() of the
  bit-matrix, fetched with the ladder scalars.  A finished lane's row is
  all-False and contributes no messages; its label row is inert (axis-1
  scatters never cross lanes) until the scheduler reuses the slot.
* **Equality**: BFS/SSSP are chaotic min-relaxations with a unique
  fixpoint, and every batched relax preserves each lane's per-round
  message multiset exactly, so batched labels are **bitwise equal** to B
  independent ``*_dd_sparse`` runs on every substrate × ndev cell
  (tests/test_multisource.py).  PPR float sums are bitwise equal per lane
  under ``operators.set_deterministic_add(True)`` (the fixed-order tree is
  vmapped per lane) and allclose otherwise.
* **Sharded**: labels are (B, n_pad) pytrees; ``ShardedGraph`` relaxes
  them with a lane-vmapped local relax + one full-mesh reduce of the whole
  lane matrix (``sharded_batched_push`` — the structured reducers degrade
  for batched lanes like they do for reversed pushes).  Sharded batched
  rounds always run the dense sweep; the union worklist path is
  single-partition.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from . import frontier as fr
from . import operators as ops
from .engine import RunStats

# the per-algorithm "unreached" labels — must match algorithms/bfs.py and
# algorithms/sssp.py exactly for the bitwise-equality contract
BFS_INF = jnp.float32(jnp.finfo(jnp.float32).max)
SSSP_INF = jnp.float32(jnp.finfo(jnp.float32).max / 4)

_scalars_jit = jax.jit(fr.batched_round_scalars)


# ---------------------------------------------------------------------------
# Batched round steps (labels pytree, (B, n_pad) frontier bit-matrix)
# ---------------------------------------------------------------------------


def _dist_dense_step(g, dist, fmat):
    new = ops.batched_push_dense(g, dist, fmat, dist, kind="min",
                                 use_weight=True)
    return new, ops.batched_updated_mask(dist, new)


def _dist_sparse_step(g, dist, fmat, *, capacity: int, budget: int):
    union = jnp.any(fmat, axis=0)
    f = fr.compact(union, capacity, g.sentinel)
    batch = ops.advance_sparse(g, f, budget)
    new = ops.batched_relax_batch(batch, dist, fmat, dist, kind="min",
                                  use_weight=True)
    return new, ops.batched_updated_mask(dist, new)


def make_ppr_steps(damping: float, tol: float):
    """Batched residual-push personalized-pagerank steps (labels =
    ``(rank, resid)`` lane matrices; the frontier row is ``resid > tol``).
    Mirrors ``pagerank.pr_push`` / ``pagerank.ppr_push`` op for op, so
    lanes are bitwise equal to per-source runs under deterministic add."""

    def _active_mass(g, rank, resid, fmat):
        outdeg = jnp.maximum(g.out_deg.astype(jnp.float32), 1.0)[None, :]
        rank = rank + jnp.where(fmat, resid, 0.0)
        push_val = jnp.where(fmat, damping * resid / outdeg, 0.0)
        return rank, push_val

    def _next_frontier(resid):
        m = resid > tol
        return m.at[:, -1].set(False)

    def dense(g, labels, fmat):
        rank, resid = labels
        rank, push_val = _active_mass(g, rank, resid, fmat)
        added = ops.batched_push_dense(g, push_val, fmat,
                                       jnp.zeros_like(resid), kind="add",
                                       use_weight=False)
        resid = jnp.where(fmat, 0.0, resid) + added
        return (rank, resid), _next_frontier(resid)

    def sparse(g, labels, fmat, *, capacity: int, budget: int):
        if ops.get_deterministic_add():
            # deterministic float-add wants ONE canonical edge order: the
            # fixed-order tree over the full edge list associates exactly
            # like the per-source dense reference, while a tree over the
            # compacted batch slots does not (same reasoning as
            # ops.sparse_round's deterministic fallback)
            return dense(g, labels, fmat)
        rank, resid = labels
        rank, push_val = _active_mass(g, rank, resid, fmat)
        union = jnp.any(fmat, axis=0)
        f = fr.compact(union, capacity, g.sentinel)
        batch = ops.advance_sparse(g, f, budget)
        added = ops.batched_relax_batch(batch, push_val, fmat,
                                        jnp.zeros_like(resid), kind="add",
                                        use_weight=False)
        resid = jnp.where(fmat, 0.0, resid) + added
        return (rank, resid), _next_frontier(resid)

    return sparse, dense


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


class MultiSourceEngine:
    """Per-round batched dispatcher over the (capacity, budget) ladder.

    ``sparse_step(g, labels, fmat, capacity=, budget=)`` and
    ``dense_step(g, labels, fmat)`` both return ``(labels, fmat)``;
    ``labels`` may be any pytree of (B, n_pad) lane matrices.  The rung is
    picked from the **union** frontier's scalars, the overflow backstop
    escalates to the dense sweep (edges are never dropped), and a sharded
    graph always relaxes dense (see module docstring).  ``round_once`` is
    the scheduler's entry point: one round for scalars the caller already
    fetched, so a serving tick pays exactly one transfer.
    """

    def __init__(self, g, sparse_step: Callable, dense_step: Callable,
                 ladder_base: int = 4):
        if getattr(g, "is_tiered", False):
            raise NotImplementedError(
                "multi-source batching needs a resident or mesh-sharded CSR")
        self.g = g
        self.plain = getattr(g, "sharded_push_dense", None) is None
        self.cap_ladder = fr.ladder_capacities(g.n_pad, g.block_size,
                                               ladder_base)
        self.budget_ladder = fr.ladder_capacities(g.m_pad, g.block_size,
                                                  ladder_base)
        self.sparse_cutoff = fr.dense_cutoff(self.budget_ladder)
        self._sparse_fn = sparse_step
        self._dense_fn = dense_step
        self._sparse = {}
        self._dense = None
        self.stats = RunStats.from_graph(g)

    # -- pinned jits (same trace-cache discipline as SparseLadderEngine) --
    def _pinned_jit(self, fn, static_argnames=()):
        sub = ops.get_substrate()
        det = ops.get_deterministic_add()

        def step(*args, **kwargs):
            with ops.substrate_scope(sub), ops.deterministic_add_scope(det):
                return fn(*args, **kwargs)

        return jax.jit(step, static_argnames=static_argnames)

    def _refresh_mode(self):
        mode = (ops.get_substrate(), ops.get_deterministic_add())
        if mode != getattr(self, "_traced_mode", None):
            self._sparse = {}
            self._dense = None
        self._traced_mode = mode
        self.stats.substrate = mode[0]

    def _get_sparse(self, cap: int, budget: int):
        key = (cap, budget)
        if key not in self._sparse:
            self.stats.compiles += 1
            self._sparse[key] = self._pinned_jit(
                self._sparse_fn, static_argnames=("capacity", "budget"))
        return self._sparse[key]

    def _get_dense(self):
        if self._dense is None:
            self.stats.compiles += 1
            self._dense = self._pinned_jit(self._dense_fn)
        return self._dense

    # -- one fetch per round: ladder scalars + per-lane termination ------
    def fetch(self, fmat):
        """``(total, ucount, umass, alive)`` in a single host transfer."""
        total, ucount, umass, alive = jax.device_get(
            _scalars_jit(self.g, fmat))
        return int(total), int(ucount), int(umass), np.asarray(alive)

    def round_once(self, labels, fmat, ucount: int, umass: int):
        """One batched round for already-fetched union scalars.

        Charges the sweep ONCE to ``edges_touched`` whatever B is — the
        amortization ledger the serving gate audits."""
        self._refresh_mode()
        g = self.g
        lanes = int(fmat.shape[0])
        self.stats.rounds += 1
        self.stats.sources = max(self.stats.sources, lanes)
        cap = fr.pick_capacity(max(ucount, 1), self.cap_ladder)
        budget = fr.pick_capacity(max(umass, 1), self.budget_ladder)
        overflow = budget < umass or cap < ucount
        if overflow and umass <= self.sparse_cutoff:
            self.stats.overflow_escalations += 1
        if not self.plain or umass > self.sparse_cutoff or overflow:
            labels, fmat = self._get_dense()(g, labels, fmat)
            self.stats.dense_rounds += 1
            self.stats.edges_touched += g.m
            self._add_batched_comm(lanes)
        else:
            labels, fmat = self._get_sparse(cap, budget)(
                g, labels, fmat, capacity=cap, budget=budget)
            self.stats.sparse_rounds += 1
            self.stats.edges_touched += budget
        return labels, fmat

    def _add_batched_comm(self, lanes: int):
        model = getattr(self.g, "batched_comm_per_relax", None)
        if model is None:
            return
        e, b, h = model(lanes)
        self.stats.comm_elems += e
        self.stats.comm_bytes += b
        self.stats.reduce_axis_hops += h

    def run(self, labels, fmat, max_rounds: int = 10_000):
        """Run every lane to termination (one scalar fetch per round)."""
        for _ in range(max_rounds):
            total, ucount, umass, _ = self.fetch(fmat)
            if total == 0:
                break
            labels, fmat = self.round_once(labels, fmat, ucount, umass)
        return labels, fmat


# ---------------------------------------------------------------------------
# Batched algorithm entry points
# ---------------------------------------------------------------------------


def ms_distances(g, sources, inf, max_rounds: int = 100_000):
    """Batched chaotic min-relaxation from B sources at once.

    Returns ``(dist, stats)`` — ``dist[b]`` is bitwise equal to the
    per-source ``*_dd_sparse`` run initialized with the same ``inf``
    (unique min-relax fixpoint + exact per-lane message multisets)."""
    src = jnp.asarray(sources, jnp.int32)
    b = int(src.shape[0])
    dist0 = jnp.full((b, g.n_pad), inf, jnp.float32)
    dist0 = dist0.at[jnp.arange(b), src].set(0.0)
    fmat0 = fr.batched_from_sources(src, g.n_pad)
    eng = MultiSourceEngine(g, _dist_sparse_step, _dist_dense_step)
    dist, _ = eng.run(dist0, fmat0, max_rounds)
    eng.stats.sources = b
    return dist, eng.stats


def ms_bfs(g, sources, max_rounds: int = 100_000):
    """Multi-source BFS (hop counts; unit weights on unweighted builders)."""
    return ms_distances(g, sources, BFS_INF, max_rounds)


def ms_sssp(g, sources, max_rounds: int = 100_000):
    """Multi-source SSSP (weighted chaotic relaxation)."""
    return ms_distances(g, sources, SSSP_INF, max_rounds)


def ms_ppr(g, sources, damping: float = 0.85, tol: float = 1e-9,
           max_rounds: int = 10_000):
    """Batched personalized pagerank: residual push from a unit of mass on
    each lane's source, normalized per lane (``pagerank.ppr_push`` is the
    single-source reference; bitwise per lane under deterministic add)."""
    src = jnp.asarray(sources, jnp.int32)
    b = int(src.shape[0])
    rank0 = jnp.zeros((b, g.n_pad), jnp.float32)
    resid0 = rank0.at[jnp.arange(b), src].set(1.0)
    fmat0 = fr.batched_from_sources(src, g.n_pad)
    sparse, dense = make_ppr_steps(damping, tol)
    eng = MultiSourceEngine(g, sparse, dense)
    (rank, resid), _ = eng.run((rank0, resid0), fmat0, max_rounds)
    rank = rank + resid
    rank = rank / jnp.sum(rank, axis=1, keepdims=True)
    valid = g.valid_vertex_mask()
    rank = jnp.where(valid[None, :], rank, 0.0)
    eng.stats.sources = b
    return rank, eng.stats
