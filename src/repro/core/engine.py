"""Round-execution engines.

Two execution regimes, mirroring the paper's §5 classification:

* ``run_dense`` — the whole algorithm is a single ``lax.while_loop`` over
  dense-frontier rounds.  One compile, no host round-trips.  This is the
  bulk-synchronous vertex-program regime every framework supports.

* ``SparseLadderEngine`` — data-driven rounds over sparse worklists along
  a (capacity, budget) rung ladder, executed **device-resident**: each
  rung's step is compiled into one jitted ``lax.while_loop`` that runs
  *consecutive same-rung rounds* entirely on device.  The carry holds the
  labels pytree, the frontier mask, the next round's ladder scalars
  (recomputed in-loop by ``frontier.round_scalars``) and int32 round /
  escalation / mass counters; the loop exits only when the frontier
  terminates or its size / edge mass leaves the rung's band (outgrows
  capacity or budget, shrinks enough that a smaller rung pays, or crosses
  the dense cutoff — ``frontier.sparse_band`` / ``dense_band`` re-derive
  the host dispatcher's decision on device).  Host syncs therefore scale
  with rung *switches* — O(ladder depth), roughly diameter-independent —
  instead of O(rounds): exactly one blocking ``jax.device_get`` per
  stretch, which fetches the previous stretch's counters and the next
  rung's scalars in a single transfer.  This is the per-round sync
  amortisation the paper's P1/P2 principles demand of a runtime (the
  blocking scalar fetch is the DIMM-latency analogue), and it is what
  lets the work-efficient engine also win wall-clock against the fused
  BSP baseline.  Dense fallback rounds fuse into band-exit stretches the
  same way.  ``SparseLadderEngine(..., fused=False)`` keeps the one-
  round-per-dispatch path — one scalar sync per round — as the measurable
  baseline, and the fused engine's ``RunStats`` counters are pinned equal
  to it (``tests/test_engine_properties.py``).

  Rung selection is unchanged by fusion.  When the frontier's median edge
  mass exceeds ``frontier.dense_cutoff`` — at most the rung below the
  top budget rung, since a sparse round on the top rung (the whole edge
  array) touches as many slots as the dense sweep and pays compaction
  besides — the engine runs the dense step (direction-optimizing style).
  On a sharded graph the ladder is **per shard**: the capacity rung is
  sized by the largest *local* frontier (active vertices with local
  edges), the budget rung by the *median* per-shard edge mass, and a
  hub-heavy shard whose mass outgrows the rung escalates alone to its
  shard-local dense relax inside the step
  (``RunStats.shard_escalations``) instead of forcing a global dense
  round; the escalation ``psum`` stays in the while_loop carry as a
  device int32, never fetched per round.  Fused stretches are jitted at
  module level with the step function and the (substrate, deterministic-
  add) mode as static arguments, so the compiled rung executables are
  shared across engine instances on the same graph — recompilation count
  is bounded by the ladder size (the "few big pages" amortisation of P2),
  and repeat runs pay zero retrace.

Both engines report work counters so benchmarks can reproduce the paper's
work-efficiency argument (Fig. 6/7): ``edges_touched`` is the number of edge
slots actually processed, which for the dense engine is m per round and for
the sparse engine is the chosen budget.  ``RunStats.substrate`` records
which relaxation substrate ("jnp" or "pallas" — see operators.py) the run
lowered through, and the ``comm_*`` counters accumulate the analytic
cross-device communication model of ``sharded.CrossReducer`` (zero for
unsharded runs).
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import frontier as fr
from . import operators as ops
from . import spans
from .graph import Graph


@dataclasses.dataclass
class RunStats:
    rounds: int = 0
    edges_touched: int = 0
    dense_rounds: int = 0
    sparse_rounds: int = 0
    compiles: int = 0
    # sparse rung couldn't cover the frontier's edge mass → the engine fell
    # back to the dense step for that round (edges are never dropped)
    overflow_escalations: int = 0
    # shards that individually escalated to their local dense relax inside
    # a sparse round (per-shard ladder overflow; 0 on a single partition)
    shard_escalations: int = 0
    # analytic cross-device communication (sharded.CrossReducer model):
    # elements / bytes crossing devices in label reductions + rebuild
    # gathers, and mesh axes traversed by reductions.  Zero when unsharded.
    comm_elems: int = 0
    comm_bytes: int = 0
    reduce_axis_hops: int = 0
    # host→device streaming of the out-of-core tiered path (core/tiered.py):
    # edge-shard bytes copied in, shards streamed (pool misses) and
    # scheduled shards already resident (pool hits).  Zero for in-memory
    # graphs, and auditable the way comm_* is: every miss copies exactly
    # one padded shard, so h2d_bytes == shards_streamed * g.shard_bytes
    # identically (pinned by tests/test_tiered.py)
    h2d_bytes: int = 0
    shards_streamed: int = 0
    buffer_hits: int = 0
    # fault-tolerance ledger of the streamed path (StreamIO.fold_delta):
    # reads retried through the tiered RetryPolicy, checksum mismatches
    # observed (healed on retry or raised as ShardCorruptError), and wall
    # time the fetch miss path spent (read + verify + H2D issue + backoff)
    io_retries: int = 0
    checksum_failures: int = 0
    io_wait_us: int = 0
    # io_wait_us by phase (host read with retries, CRC32, device_put
    # issue), the misses begun with the device drained of their relax,
    # and the longest single miss of the run (a maximum, not a sum)
    read_us: int = 0
    crc_us: int = 0
    put_us: int = 0
    fetch_exposed_us: int = 0
    fetch_max_us: int = 0
    # SparseLadderEngine: edge slots the sparse rounds charged (part of
    # edges_touched; exact), and host time from each stretch's dispatch to
    # the blocking fetch that settles it, summed by regime (the
    # ``engine.stretch`` spans' durations)
    sparse_edges_touched: int = 0
    sparse_us: int = 0
    dense_us: int = 0
    # blocking fetches that settled a stretch, one per closed
    # ``engine.stretch`` span: one per stretch fused, one per round
    # otherwise (a host-path count, so the two paths differ in it)
    stretches: int = 0
    # direction-optimizing traversal: rounds executed in the pull (CSC)
    # direction — those are charged by in-degree scan mass, not m
    pull_rounds: int = 0
    # concurrent source lanes the run's sweeps were amortized over
    # (core/multisource.py batches; 1 for every per-query engine) —
    # edges_touched / sources is the per-source cost the serving gate keys on
    sources: int = 1
    # execution geometry: device count and placement policy of the graph the
    # run executed on (1/"local" for an unsharded Graph)
    ndev: int = 1
    placement: str = "local"
    # relaxation backend the run lowered through (operators.get_substrate())
    substrate: str = dataclasses.field(default_factory=ops.get_substrate)

    @classmethod
    def from_graph(cls, g, relaxes: int = 0, **kw) -> "RunStats":
        """Stats pre-filled with the graph's execution geometry (works for
        both ``Graph`` and ``sharded.ShardedGraph``).  ``relaxes`` charges
        that many cross-device label reductions to the comm counters —
        algorithms built on ``run_dense`` pass their round count."""
        st = cls(ndev=getattr(g, "ndev", 1),
                 placement=getattr(g, "placement", "local"), **kw)
        st.add_comm(g, relaxes)
        return st

    def add_comm(self, g, relaxes: int = 1, scalar_collectives: int = 0,
                 reverse: bool = False):
        """Accumulate the analytic comm model for ``relaxes`` label
        reductions on ``g`` (no-op for an unsharded ``Graph``), plus any
        scalar flag collectives (charged as one element per device pair).
        ``reverse`` charges reversed-scatter relaxes at the reverse-safe
        reducer's rate (cvc2d executes them full-mesh)."""
        model = getattr(g, "comm_per_relax", None)
        if model is None:
            return
        e, b, h = model(reverse=True) if reverse else model()
        d = getattr(g, "ndev", 1)
        flag = scalar_collectives * d * (d - 1) if d > 1 else 0
        self.comm_elems += e * relaxes + flag
        self.comm_bytes += b * relaxes + flag * 4
        self.reduce_axis_hops += h * relaxes

    def as_dict(self):
        return dataclasses.asdict(self)


def run_dense(
    step: Callable,
    state,
    cond: Callable,
    max_rounds: int,
):
    """``state = step(state)`` while ``cond(state)``, fused in one while_loop.

    ``state`` must carry its own round counter if the step needs one.
    """

    def body(carry):
        r, s = carry
        return r + 1, step(s)

    def keep_going(carry):
        r, s = carry
        return jnp.logical_and(r < max_rounds, cond(s))

    rounds, out = jax.lax.while_loop(keep_going, body, (jnp.int32(0), state))
    return rounds, out


def resume_run(checkpointer, state_like):
    """``(state, start_round)`` for a run that may be resuming: the
    checkpointer's latest snapshot re-placed on device, or the caller's
    fresh ``state_like`` and round 0.  The returned round is the round the
    snapshot was taken AFTER — the engine executes rounds
    ``start_round..max_rounds`` and, because the fold order is
    deterministic, finishes bitwise identical to the uninterrupted run
    (``tests/test_chaos.py`` kills a subprocess mid-run to prove it)."""
    if checkpointer is None:
        return state_like, 0
    state, start = checkpointer.load(state_like)
    if start:
        state = jax.device_put(state)
    return state, start


def run_host(
    step: Callable,
    state,
    cond: Callable,
    max_rounds: int,
    checkpointer=None,
    fault=None,
):
    """Eager counterpart of ``run_dense`` for graphs whose relaxation
    cannot be traced into a while_loop — the tiered out-of-core path
    (``core/tiered.py``) issues H2D copies and walks a host-side buffer
    pool inside each step, so rounds dispatch from Python with one
    blocking ``cond`` fetch per round (the streamed regime pays per-round
    syncs; what it buys is edges never resident).  Same
    ``(rounds, state)`` contract as ``run_dense``.

    Because rounds dispatch from Python anyway, this is also the regime
    where mid-run fault tolerance is free to bolt on: ``checkpointer`` (a
    ``checkpoint.RunCheckpointer``) resumes from its latest snapshot and
    snapshots ``state`` every ``every`` rounds; ``fault`` (a
    ``core.faultio.FaultInjector``) ticks the ``"round"`` site per round
    so chaos drills can kill/delay a run at an exact round.
    ``max_rounds`` is the TOTAL run budget — a run resumed at round r
    executes at most ``max_rounds - r`` more."""
    state, rounds = resume_run(checkpointer, state)
    while rounds < max_rounds and bool(cond(state)):
        with spans.Span("engine.round", round=rounds):
            if fault is not None:
                fault.tick("round", key=rounds)
            state = step(state)
            rounds += 1
            if checkpointer is not None:
                checkpointer.maybe_save(state, rounds)
    return rounds, state


# ---------------------------------------------------------------------------
# Streamed execution (out-of-core tiered graphs)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("step", "cond", "active", "sub", "det"))
def _staged_stretch(sg, state, limit, *, step, cond, active, sub, det):
    """Run consecutive rounds over a pre-staged live shard set
    (``tiered.StagedShards``) as one device-resident band-exit while_loop
    — the streamed twin of ``_sparse_stretch`` / ``_dense_stretch``.

    The band is live-set stability (``frontier.live_stable``): the loop
    keeps executing while the frontier stays alive AND its live-shard set
    still equals the staged set, and exits the moment the host scheduler
    would stream a different shard schedule.  The ``first`` flag
    guarantees the round the host staged for always executes (its live
    set equals the staged set by construction).  Returns
    ``(state, rounds_run)``; the caller fetches the round count together
    with the NEXT round's scalars in one transfer.
    """
    with ops.substrate_scope(sub), ops.deterministic_add_scope(det):
        def keep(c):
            first, st, k = c
            return ((k < limit) & cond(st)
                    & (first | fr.live_stable(sg, active(sg, st))))

        def body(c):
            _, st, k = c
            return jnp.bool_(False), step(sg, st), k + 1

        _, state, k = jax.lax.while_loop(
            keep, body, (jnp.bool_(True), state, jnp.int32(0)))
        return state, k


@lru_cache(maxsize=None)
def _streamed_step_for(dense_fn):
    """Adapt an engine ``(g, labels, mask) -> (labels, mask)`` dense step
    to ``run_streamed``'s ``(g, state) -> state`` shape.  Cached so the
    adapter has stable identity per dense step — ``_staged_stretch`` jits
    with the step as a static argument, and a fresh closure per run would
    defeat the trace cache."""
    def step(gr, state):
        labels, mask = state
        return dense_fn(gr, labels, mask)
    return step


def _mask_cond(state):
    """Termination for (labels, mask) streamed states: frontier alive."""
    return jnp.any(state[1])


def _mask_active(gr, state):
    """Schedule mask for (labels, mask) streamed states."""
    return state[1]


def run_streamed(
    g,
    step: Callable,    # (graph_or_staged, state) -> state
    state,
    cond: Callable,    # (state,) -> device bool
    active: Callable,  # (graph_or_staged, state) -> (n_pad,) bool mask
    max_rounds: int,
    *,
    checkpointer=None,
    fused: bool = True,
    on_rounds: Callable = None,  # (k, live) host callback per retired batch
    ckpt_stats: Callable = None,
):
    """Generic runner for a ``tiered.TieredGraph``: frontier-driven shard
    streaming, with device-resident rung-fused stretches when the live
    shard set is stable.

    Each trip fetches ``(cond, frontier_count, live_shard_mask)`` in ONE
    transfer.  When ``fused`` and the live set fits the buffer pool, the
    set is pre-staged (``g.stage``) and the next rounds run as one jitted
    ``_staged_stretch`` — its round count rides back with the NEXT trip's
    scalars, so a stretch costs the same single blocking fetch an eager
    round does and host syncs scale with live-set *switches*.  Rounds
    whose live set outgrows the pool (the LRU pool restreams by design)
    fall back to one eager round, as does the whole run when a fault
    injector is attached (kill drills need the per-round ``"round"`` tick)
    or ``fused=False`` (the measurable per-round baseline).  Labels are
    bitwise identical across all three regimes: a staged stretch folds the
    same shards in the same ascending order as the eager rounds it
    replaces (``tests/test_tiered_properties.py`` pins this).

    ``on_rounds(k, live)`` reports every retired batch of ``k`` rounds
    that all ran over schedule ``live`` — exact per-round classification,
    since a stretch exits on any live-set change.  Returns
    ``(rounds, state)``; ``checkpointer`` snapshots at the same host
    boundaries the syncs already pay for.
    """
    state, rnd = resume_run(checkpointer, state)
    fault = getattr(g, "fault", None)
    use_fused = fused and fault is None
    sub, det = ops.get_substrate(), ops.get_deterministic_add()

    def settle(k, live):
        nonlocal rnd
        k = int(k)
        g.charge_staged_rounds(k, live)
        if on_rounds is not None:
            on_rounds(k, live)
        rnd += k
        if checkpointer is not None:
            checkpointer.maybe_save(
                state, rnd, None if ckpt_stats is None else ckpt_stats())

    pending = None  # (rounds_run device int32, live) of the stretch in flight
    while rnd < max_rounds:
        scal = (cond(state), *g.round_live(active(g, state)))
        if pending is None:
            go, count, live = jax.device_get(scal)
        else:
            # ONE blocking fetch settles the in-flight stretch AND picks
            # the next schedule
            go, count, live, k = jax.device_get((*scal, pending[0]))
            settle(k, pending[1])
            pending = None
            if rnd >= max_rounds:
                break
        if not bool(go) or int(count) == 0:
            break
        live = np.asarray(live)
        sg = g.stage(live) if use_fused else None
        if sg is None:
            # eager round: live set dead-ends or outgrows the pool, the
            # baseline was requested, or a fault plan needs round ticks
            if fault is not None:
                fault.tick("round", key=rnd)
            g.set_live_hint(live)
            state = step(g, state)
            rnd += 1
            if on_rounds is not None:
                on_rounds(1, live)
            if checkpointer is not None:
                checkpointer.maybe_save(
                    state, rnd, None if ckpt_stats is None else ckpt_stats())
        else:
            state, k_dev = _staged_stretch(
                sg, state, jnp.int32(max_rounds - rnd), step=step, cond=cond,
                active=active, sub=sub, det=det)
            pending = (k_dev, live)
    if pending is not None:
        k, live = jax.device_get(pending[0]), pending[1]
        settle(k, live)
    return rnd, state


# ---------------------------------------------------------------------------
# Device-resident rung stretches
# ---------------------------------------------------------------------------
# One jitted band-exit while_loop per (rung, regime).  Jitted at module
# level with the step callable and the (substrate, deterministic-add) mode
# as *static* arguments: the trace cache keys on them, so a mode flip gets
# a fresh trace by construction (no per-engine cache invalidation needed —
# contrast the per-round path's ``_pinned_jit``) and engine instances on
# the same graph share compiled rung executables across runs.
#
# Both runners are do-while loops: the ``first`` carry flag guarantees the
# round the host dispatched for always executes, even when its scalars sit
# outside the band (the overflow backstop enters dense below the cutoff);
# every later round runs only while the band predicate re-derives the same
# host decision.  ``limit`` caps the stretch at the caller's remaining
# ``max_rounds`` budget.  All counters stay device int32s; nothing in
# either loop body touches the host.


@partial(jax.jit, static_argnames=("step", "capacity", "budget", "lo_cap",
                                   "lo_budget", "cutoff", "sub", "det"))
def _sparse_stretch(g, labels, mask, scalars, limit, *, step, capacity,
                    budget, lo_cap, lo_budget, cutoff, sub, det):
    """Run consecutive (capacity, budget)-rung sparse rounds on device.

    Returns ``(labels, mask, scalars, rounds, escalations)`` — ``scalars``
    already describes the *next* round, so the host's single fetch per
    stretch covers both settling this stretch and picking the next rung.
    """
    with ops.substrate_scope(sub), ops.deterministic_add_scope(det):
        def cond(c):
            first, _, _, sc, k, _ = c
            band = fr.sparse_band(sc, capacity, lo_cap, budget, lo_budget,
                                  cutoff)
            return (k < limit) & (first | band)

        def body(c):
            _, labels, mask, _, k, esc = c
            labels, mask, e = step(g, labels, mask, capacity=capacity,
                                   budget=budget)
            return (jnp.bool_(False), labels, mask,
                    fr.round_scalars(g, mask), k + 1,
                    esc + jnp.asarray(e, jnp.int32))

        _, labels, mask, scalars, k, esc = jax.lax.while_loop(
            cond, body,
            (jnp.bool_(True), labels, mask, scalars, jnp.int32(0),
             jnp.int32(0)))
        return labels, mask, scalars, k, esc


@partial(jax.jit, static_argnames=("step", "cutoff", "sub", "det"))
def _dense_stretch(g, labels, mask, scalars, limit, *, step, cutoff, sub,
                   det):
    """Run consecutive dense-fallback rounds on device.

    ``mass`` accumulates each round's *entry* frontier edge mass (the work
    the relax actually expands) so ``dense_cost="mass"`` accounting matches
    the per-round engine exactly.  Returns ``(labels, mask, scalars,
    rounds, mass)``.
    """
    with ops.substrate_scope(sub), ops.deterministic_add_scope(det):
        def cond(c):
            first, _, _, sc, k, _ = c
            return (k < limit) & (first | fr.dense_band(sc, cutoff))

        def body(c):
            _, labels, mask, sc, k, mass = c
            mass = mass + sc[3]
            labels, mask = step(g, labels, mask)
            return (jnp.bool_(False), labels, mask,
                    fr.round_scalars(g, mask), k + 1, mass)

        _, labels, mask, scalars, k, mass = jax.lax.while_loop(
            cond, body,
            (jnp.bool_(True), labels, mask, scalars, jnp.int32(0),
             jnp.int32(0)))
        return labels, mask, scalars, k, mass


# initial ladder scalars (later stretches return next-round scalars in
# their carry, so this runs once per engine run, not once per round)
_round_scalars = jax.jit(fr.round_scalars)


class SparseLadderEngine:
    """Dispatches device-resident rung stretches along a (capacity, budget)
    ladder (``fused=False`` keeps one jitted step dispatch per round)."""

    def __init__(
        self,
        g: Graph,
        sparse_step: Callable,  # (g, labels, mask, capacity, budget) -> (labels, mask, esc)
        dense_step: Callable,   # (g, labels, frontier_mask) -> (labels, mask)
        ladder_base: int = 4,
        budget_factor: int = 4,
        dense_cost: str = "m",
        fused: bool = True,
    ):
        # ``labels`` may be any pytree (kcore threads an (alive, degree)
        # pair); only ``mask`` must be an (n_pad,) bool frontier bitmap.
        # ``dense_cost`` selects what a dense round charges to
        # ``edges_touched``: ``"m"`` (every edge slot — the relax really
        # touches all of them) or ``"mass"`` (the frontier's out-degree
        # mass — the paper's work-efficiency convention for peel-style
        # algorithms whose dense rounds are still frontier-driven).
        # ``fused`` selects device-resident rung stretches (the default;
        # host syncs = O(rung switches)) vs one dispatch + scalar sync per
        # round (the measurable baseline; both produce identical labels
        # AND identical RunStats counters).  The step callables should
        # have stable identity (module-level functions or cached
        # closures): fused stretches are jitted with the step as a static
        # argument, so fresh closures per engine defeat trace-cache reuse
        # across runs.
        assert dense_cost in ("m", "mass"), dense_cost
        self.dense_cost = dense_cost
        self.fused = fused
        self._stretch_keys = set()
        self.g = g
        self.cap_ladder = fr.ladder_capacities(g.n_pad, g.block_size, ladder_base)
        # budgets are per merge-path expansion: per-device on a sharded
        # graph (each shard expands its local frontier over its own epd
        # edges), whole-graph otherwise
        shard_edges = getattr(g, "epd", g.m_pad)
        self.budget_ladder = fr.ladder_capacities(shard_edges, g.block_size,
                                                  ladder_base)
        self.budget_factor = budget_factor
        self._sparse = {}
        self._dense = None
        self._sparse_fn = sparse_step
        self._dense_fn = dense_step
        self.stats = RunStats.from_graph(g)

    def _pinned_jit(self, fn, static_argnames=()):
        """jit ``fn`` with the current substrate / deterministic-add mode
        pinned into the trace.

        The pinning closure is created fresh per cache entry on purpose:
        JAX shares trace caches across ``jax.jit`` wrappers of the *same*
        function object, so re-wrapping ``self._sparse_fn`` after a
        substrate flip would silently reuse the old backend's trace (while
        RunStats reported the new one).  A fresh closure has fresh identity,
        and re-entering the scopes at trace time makes the step read the
        mode it was cached under, not whatever is globally current.
        """
        sub = ops.get_substrate()
        det = ops.get_deterministic_add()

        def step(*args, **kwargs):
            with ops.substrate_scope(sub), ops.deterministic_add_scope(det):
                return fn(*args, **kwargs)

        return jax.jit(step, static_argnames=static_argnames)

    def _get_sparse(self, cap: int, budget: int):
        key = (cap, budget)
        if key not in self._sparse:
            self.stats.compiles += 1
            self._sparse[key] = self._pinned_jit(
                self._sparse_fn, static_argnames=("capacity", "budget")
            )
        return self._sparse[key]

    def _get_dense(self):
        if self._dense is None:
            self.stats.compiles += 1
            self._dense = self._pinned_jit(self._dense_fn)
        return self._dense


    def run(self, labels, mask, max_rounds: int = 10_000, checkpointer=None):
        # ``checkpointer`` (checkpoint.RunCheckpointer): resume from its
        # latest snapshot and snapshot (labels, mask) every ``every``
        # rounds; ``max_rounds`` stays the TOTAL run budget across
        # interruptions.  Works in all three regimes — the fused path
        # snapshots at stretch boundaries (its only host syncs).
        if getattr(self.g, "is_tiered", False):
            return self._run_streamed(labels, mask, max_rounds, checkpointer)
        if self.fused:
            return self._run_fused(labels, mask, max_rounds, checkpointer)
        return self._run_per_round(labels, mask, max_rounds, checkpointer)

    # ---- streamed dispatch (out-of-core tiered graphs) -----------------

    def _run_streamed(self, labels, mask, max_rounds: int,
                      checkpointer=None):
        """Streamed dispatch for a ``tiered.TieredGraph`` — the engine's
        resident-budget path, delegated to the generic ``run_streamed``:
        the CSR lives behind a bounded pool of device shard buffers, the
        runner fetches ``(cond, frontier_count, live_shard_mask)`` in ONE
        transfer per trip (``round_live`` — the rung-scalar analogue), and
        stable live sets that fit the pool fuse into device-resident
        stretches (``_staged_stretch``).  ``self.fused=False`` keeps the
        one-eager-round-per-trip baseline.  Rounds that leave shards idle
        count as sparse (shard-granular work-efficiency ⇒
        bandwidth-efficiency); rounds touching every shard count as dense
        — a stretch's rounds all share one schedule, so the
        classification stays per-round exact.  Stream deltas fold into
        ``h2d_bytes`` / ``shards_streamed`` / ``buffer_hits`` /
        ``edges_touched`` at the end.

        This is also the crash-recovery regime (the paper's months-lived
        persistent store): ``checkpointer`` snapshots ``(labels, mask)``
        at the host boundaries the syncs already pay for and resumes
        bitwise, and a graph with an attached ``FaultInjector`` runs
        eager so kill drills land at an exact round."""
        g = self.g
        self.stats.substrate = ops.get_substrate()
        io0 = g.io.snapshot()

        def on_rounds(k, live):
            self.stats.rounds += k
            if int(live.sum()) < g.nshards:
                self.stats.sparse_rounds += k
            else:
                self.stats.dense_rounds += k

        _, (labels, mask) = run_streamed(
            g, _streamed_step_for(self._dense_fn), (labels, mask),
            _mask_cond, _mask_active, max_rounds,
            checkpointer=checkpointer, fused=self.fused,
            on_rounds=on_rounds, ckpt_stats=self.stats.as_dict)
        g.io.fold_delta(self.stats, io0)
        return labels, mask

    # ---- device-resident rung execution (the default) -----------------

    def _note_stretch(self, key):
        """``compiles`` counts distinct stretch traces *this engine*
        requested (≤ ladder² × regimes, the P2 amortisation bound); the
        process-wide jit cache may satisfy them without recompiling."""
        if key not in self._stretch_keys:
            self._stretch_keys.add(key)
            self.stats.compiles += 1

    def _settle_stretch(self, regime, budget, k, esc, dmass):
        """Fold one fetched stretch (k rounds) into RunStats — the exact
        per-round accumulation, summed in closed form."""
        g = self.g
        self.stats.rounds += k
        if regime == "dense":
            self.stats.dense_rounds += k
            self.stats.edges_touched += (
                dmass if self.dense_cost == "mass" else k * g.m)
            self.stats.add_comm(g, relaxes=k)
        else:
            ndev = self.stats.ndev
            epd = getattr(g, "epd", g.m_pad)
            self.stats.sparse_rounds += k
            self.stats.shard_escalations += esc
            # per round: budget·(ndev − esc_r) + epd·esc_r, summed over k
            slots = budget * (k * ndev - esc) + epd * esc
            self.stats.edges_touched += slots
            self.stats.sparse_edges_touched += slots
            self.stats.add_comm(g, relaxes=k, scalar_collectives=k)

    def _close_stretch(self, regime, span):
        """End a stretch's ``engine.stretch`` span at the blocking fetch
        that settled it, count that fetch, and charge its host time to the
        regime's timer."""
        us = span.close() // 1000
        self.stats.stretches += 1
        if regime == "dense":
            self.stats.dense_us += us
        else:
            self.stats.sparse_us += us

    def _run_fused(self, labels, mask, max_rounds: int, checkpointer=None):
        g = self.g
        sub = ops.get_substrate()
        det = ops.get_deterministic_add()
        self.stats.substrate = sub
        sparse_cutoff = fr.dense_cutoff(self.budget_ladder)
        (labels, mask), round_no = resume_run(checkpointer, (labels, mask))
        scalars = _round_scalars(g, mask)
        pending = None  # (regime, budget, span) of the stretch in flight
        counters = None
        rounds_left = max_rounds - round_no
        while True:
            # ONE blocking fetch per stretch: the in-flight stretch's
            # counters and the next round's ladder scalars come back in a
            # single transfer (the stretch keeps executing under async
            # dispatch until this point)
            if pending is None:
                count, cap_need, mass_med, _ = (
                    int(x) for x in jax.device_get(scalars))
            else:
                sc, cnt = jax.device_get((scalars, counters))
                self._close_stretch(pending[0], pending[2])
                count, cap_need, mass_med, _ = (int(x) for x in sc)
                k, esc, dmass = (int(x) for x in cnt)
                self._settle_stretch(pending[0], pending[1], k, esc, dmass)
                rounds_left -= k
                round_no += k
                pending = None
                # snapshot at the stretch boundary — the fused path's only
                # host sync, so checkpointing adds no extra round-trips
                # (rounds covered by one stretch may jump past a multiple
                # of ``every``; maybe_save's since-last rule handles it)
                if checkpointer is not None:
                    checkpointer.maybe_save((labels, mask), round_no,
                                            self.stats.as_dict())
            if count == 0 or rounds_left <= 0:
                break
            cap = fr.pick_capacity(max(cap_need, 1), self.cap_ladder)
            budget = fr.pick_capacity(max(mass_med, 1), self.budget_ladder)
            # unreachable when pick_capacity honours the ladder contract
            # (rung ≥ requested); kept as the overflow backstop — the
            # do-while stretch then runs exactly one dense round
            overflow = budget < mass_med or cap < cap_need
            if overflow and mass_med <= sparse_cutoff:
                self.stats.overflow_escalations += 1
            limit = jnp.int32(rounds_left)
            if mass_med > sparse_cutoff or overflow:
                # the stretch's device-side mass accumulator is an int32
                # and each round adds ≤ m: cap the stretch so the sum
                # cannot wrap (per-round dispatch sums the same values in
                # unbounded Python ints — the counters must stay equal).
                # Only enormous graphs ever shorten a stretch: m = 1e6
                # caps at 2147 dense rounds per fetch
                mass_cap = max(1, (2**31 - 1) // max(g.m, 1))
                limit = jnp.int32(min(rounds_left, mass_cap))
                self._note_stretch(("dense", sub, det))
                span = spans.Span("engine.stretch", regime="dense",
                                  capacity=0, budget=0)
                labels, mask, scalars, k_dev, mass_dev = _dense_stretch(
                    g, labels, mask, scalars, limit, step=self._dense_fn,
                    cutoff=sparse_cutoff, sub=sub, det=det)
                pending = ("dense", 0, span)
                counters = (k_dev, jnp.int32(0), mass_dev)
            else:
                self._note_stretch(("sparse", cap, budget, sub, det))
                span = spans.Span("engine.stretch", regime="sparse",
                                  capacity=cap, budget=budget)
                labels, mask, scalars, k_dev, esc_dev = _sparse_stretch(
                    g, labels, mask, scalars, limit, step=self._sparse_fn,
                    capacity=cap, budget=budget,
                    lo_cap=fr.ladder_below(cap, self.cap_ladder),
                    lo_budget=fr.ladder_below(budget, self.budget_ladder),
                    cutoff=sparse_cutoff, sub=sub, det=det)
                pending = ("sparse", budget, span)
                counters = (k_dev, esc_dev, jnp.int32(0))
        return labels, mask

    # ---- per-round dispatch (the measurable baseline) ------------------

    def _run_per_round(self, labels, mask, max_rounds: int,
                       checkpointer=None):
        g = self.g
        # cached steps were pinned to the (substrate, deterministic-add)
        # mode active when they were jitted; if the engine-wide selection
        # changed since, drop them so the run actually executes (and
        # reports) the current backend
        mode = (ops.get_substrate(), ops.get_deterministic_add())
        if mode != getattr(self, "_traced_mode", None):
            self._sparse = {}
            self._dense = None
        self._traced_mode = mode
        self.stats.substrate = ops.get_substrate()
        ndev = self.stats.ndev
        epd = getattr(g, "epd", g.m_pad)
        # largest mass that runs sparse: the top budget rung never does,
        # since it costs at least the dense sweep
        sparse_cutoff = fr.dense_cutoff(self.budget_ladder)
        (labels, mask), rnd = resume_run(checkpointer, (labels, mask))
        dense_span = None  # a dense round settles at the next fetch
        while rnd < max_rounds:
            count, cap_need, mass_med, mass_tot = (
                int(x) for x in jax.device_get(_round_scalars(g, mask)))
            if dense_span is not None:
                self._close_stretch("dense", dense_span)
                dense_span = None
            if count == 0:
                break
            self.stats.rounds += 1
            cap = fr.pick_capacity(max(cap_need, 1), self.cap_ladder)
            # budget rung sized for the TYPICAL shard (median mass): light
            # shards stop paying for the heaviest one, and a hub-heavy
            # shard escalates alone inside the step (shard_escalations)
            budget = fr.pick_capacity(max(mass_med, 1), self.budget_ladder)
            # a rung that cannot hold what it was picked for would silently
            # drop work — escalate to the dense step instead.  Unreachable
            # when pick_capacity honours the ladder contract (rung >=
            # requested); kept as the overflow backstop.
            overflow = budget < mass_med or cap < cap_need
            if overflow and mass_med <= sparse_cutoff:
                self.stats.overflow_escalations += 1
            # the dense fallback keys on the TYPICAL shard: when only a
            # hub-heavy minority outgrows the rung, the round stays sparse
            # and those shards escalate locally inside the step
            if mass_med > sparse_cutoff or overflow:
                dense_span = spans.Span("engine.stretch", regime="dense",
                                        capacity=0, budget=0)
                labels, mask = self._get_dense()(g, labels, mask)
                self.stats.dense_rounds += 1
                self.stats.edges_touched += (
                    mass_tot if self.dense_cost == "mass" else g.m)
                self.stats.add_comm(g, relaxes=1)
            else:
                span = spans.Span("engine.stretch", regime="sparse",
                                  capacity=cap, budget=budget)
                labels, mask, esc = self._get_sparse(cap, budget)(
                    g, labels, mask, capacity=cap, budget=budget
                )
                esc = int(esc)  # the blocking fetch that settles the round
                self._close_stretch("sparse", span)
                slots = budget * (ndev - esc) + epd * esc
                self.stats.shard_escalations += esc
                self.stats.sparse_rounds += 1
                self.stats.edges_touched += slots
                self.stats.sparse_edges_touched += slots
                self.stats.add_comm(g, relaxes=1, scalar_collectives=1)
            rnd += 1
            if checkpointer is not None:
                checkpointer.maybe_save((labels, mask), rnd,
                                        self.stats.as_dict())
        if dense_span is not None:
            # the round budget ran out with no fetch left to settle it
            self._close_stretch("dense", dense_span)
        return labels, mask
