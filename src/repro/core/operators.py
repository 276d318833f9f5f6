"""Edge relaxation operators: push, pull, and load-balanced sparse advance.

These are the engine's "operator" layer in the paper's classification (§5.1):

* ``push_dense``  — push-style operator applied to *all* edges, masked by an
  active-source bitmap.  Cost O(m).  This is what topology-driven and
  dense-worklist data-driven algorithms use.
* ``pull_dense``  — pull-style operator over in-edges (CSC required).
* ``advance_sparse`` — data-driven push from a compacted ``SparseFrontier``
  with **merge-path load balancing**: the ``budget`` edge slots are assigned
  to frontier vertices by binary search over the running degree sum, so a
  3M-degree hub and a degree-1 leaf cost the same per-slot work (this is the
  TPU/static-shape rendition of Galois's per-thread chunked worklists; on
  GPUs the same trick is known from merge-based SpMV).  Cost O(budget).
* ``relax_edges`` — full edge list under a **per-edge** validity mask, for
  algorithms whose activation is a property of the edge, not the source
  vertex (delta-stepping's light/heavy split).
* ``intersect_batch`` — triangle counting's oriented sorted-intersection
  count over an edge batch (exact int32; bitwise identical everywhere).
* ``direction_choice`` — Beamer's α/β heuristic for direction-optimizing
  traversal, used by bfs_dirop (the paper's §5.2 comparison point).

``push_dense(..., reverse=True)`` pushes along reversed edges (gather at
the destination, scatter into the source) — bc's backward dependency sweep
— without materialising a CSC mirror.

Every relaxation op lowers through a selectable **substrate**:

* ``"jnp"``    — XLA scatter and sorted segmented scans
  (``kernels/graph_ops/ref.py``, the reference semantics); its dense
  push reduces over the CSC mirror where there is one;
* ``"pallas"`` — the blocked Pallas kernels in ``kernels/graph_ops/``
  (``interpret=True`` on CPU).  The TPU compiler refuses all four kernels
  today (``tests/test_tpu_compile.py``), so on a TPU this substrate fails
  with the compiler's error; ``"jnp"`` is the path that runs there.

Select globally with ``set_substrate("pallas")`` / the ``substrate_scope``
context manager, or per call via the ``substrate=`` argument.  The
process-wide default comes from the ``REPRO_SUBSTRATE`` env var (CI runs
the tier-1 suite under both).  Algorithms and engines run unmodified on
either; ``RunStats.substrate`` records which one a run used.  The selection
is read at trace time, so don't flip it under a cached jitted step of your
own.  ``run_dense`` traces its while_loop at every call, and
``SparseLadderEngine`` pins the mode into each cached step via a fresh
closure and re-pins when the selection flips (JAX shares trace caches
across ``jit`` wrappers of the same function object, so merely re-jitting
a module-level step would silently reuse the old backend's trace) — which
is why those run unmodified.

Two orthogonal execution modes layer on top of the substrate seam:

* **Sharded dispatch** — handing any relaxation op a
  ``sharded.ShardedGraph`` (or ``ShardedEdgeBatch``) routes it through the
  shard_map path in ``core/sharded.py``: shard-local relax through the
  selected substrate, then a cross-device label reduction.
* **Deterministic ``add``** — ``set_deterministic_add(True)`` /
  ``deterministic_add_scope()`` makes every ``kind="add"`` reduction use
  one fixed-order segmented tree reduction (``graph_ops.det_scatter_add``)
  on *both* substrates, so float accumulations (pagerank) are bitwise
  reproducible across backends.  Costs a stable sort per relax; off by
  default.  Not yet applied under sharded dispatch, where per-shard psum
  order still depends on the partition (see ROADMAP).
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Tuple

import jax
import jax.numpy as jnp

from ..kernels import graph_ops as gk
from ..kernels.graph_ops import neutral_for, scatter_reduce  # noqa: F401 (re-export)
from . import frontier as fr
from .frontier import DenseFrontier, SparseFrontier
from .graph import Graph

SUBSTRATES = ("jnp", "pallas")
DEFAULT_SUBSTRATE = os.environ.get("REPRO_SUBSTRATE", "jnp")
if DEFAULT_SUBSTRATE not in SUBSTRATES:
    raise ValueError(
        f"REPRO_SUBSTRATE={DEFAULT_SUBSTRATE!r} is not one of {SUBSTRATES}")
_substrate = DEFAULT_SUBSTRATE
_deterministic_add = False


def set_substrate(name: str) -> None:
    """Select the engine-wide relaxation substrate ("jnp" or "pallas")."""
    global _substrate
    if name not in SUBSTRATES:
        raise ValueError(f"unknown substrate {name!r}; pick from {SUBSTRATES}")
    _substrate = name


def get_substrate() -> str:
    return _substrate


@contextlib.contextmanager
def substrate_scope(name: str):
    """Temporarily select a substrate: ``with substrate_scope("pallas"): ...``"""
    prev = get_substrate()
    set_substrate(name)
    try:
        yield
    finally:
        set_substrate(prev)


def _resolve(substrate) -> str:
    if substrate is None:
        return _substrate
    if substrate not in SUBSTRATES:
        raise ValueError(f"unknown substrate {substrate!r}; pick from {SUBSTRATES}")
    return substrate


def set_deterministic_add(on: bool) -> None:
    """Route every ``kind="add"`` relaxation (all substrates) through the
    fixed-order segmented tree reduction so float sums are bitwise
    backend-reproducible.  Read at trace time, like the substrate."""
    global _deterministic_add
    _deterministic_add = bool(on)


def get_deterministic_add() -> bool:
    return _deterministic_add


@contextlib.contextmanager
def deterministic_add_scope(on: bool = True):
    prev = _deterministic_add
    set_deterministic_add(on)
    try:
        yield
    finally:
        set_deterministic_add(prev)


def push_dense(
    g: Graph,
    src_val: jax.Array,
    active: jax.Array,
    out_init: jax.Array,
    kind: str = "min",
    use_weight: bool = True,
    substrate: str | None = None,
    reverse: bool = False,
) -> jax.Array:
    """Relax every edge whose source is active.

    ``src_val``: (n_pad,) value carried by each source vertex.
    ``active``: (n_pad,) bool mask (sentinel must be False).
    ``out_init``: (n_pad,) accumulator initial value.
    Message is ``src_val[src] + w`` for min/max ("tropical" relax) and
    ``src_val[src] * w`` for add (weighted contribution).

    ``reverse=True`` pushes along the *reversed* edges without needing a
    CSC mirror: the message is gathered from each edge's destination and
    scattered into its source (bc's backward dependency sweep).  On a
    2-D-cut ``ShardedGraph`` the reversed scatter breaks the column-
    ownership invariant the CVC reducer exploits, so that cell degrades
    to the full-mesh reduce (owner-targeted 1-D reduce-scatter is a full
    reduction and stays).
    """
    sub = _resolve(substrate)
    tiered = getattr(g, "tiered_push_dense", None)
    if tiered is not None:
        # out-of-core dispatch (core/tiered.py): stream the shards the
        # active mask touches through the bounded device buffer pool; the
        # deterministic-add mode is folded per shard in ascending shard
        # order (pool-size independent — see the module's reduction-order
        # contract)
        return tiered(src_val, active, out_init, kind, use_weight, sub,
                      reverse=reverse, det=(kind == "add" and
                                            _deterministic_add))
    sharded = getattr(g, "sharded_push_dense", None)
    if sharded is not None:
        if kind == "add" and _deterministic_add:
            # canonical-order fixed tree over the flat edge multiset:
            # bitwise identical across placement × ndev AND to the
            # single-device deterministic path (see sharded._det_add_flat)
            return g.sharded_det_push(src_val, active, out_init, use_weight,
                                      reverse)
        return sharded(src_val, active, out_init, kind, use_weight, sub,
                       reverse)
    s, d = (g.col_idx, g.src_idx) if reverse else (g.src_idx, g.col_idx)
    if kind == "add" and _deterministic_add:
        return gk.det_push_ref(s, d, g.edge_w, src_val,
                               active, out_init, use_weight)
    if sub == "pallas":
        return gk.edge_relax(
            s, d, g.edge_w, active, src_val, out_init,
            kind=kind, use_weight=use_weight, vertex_mask=True,
        )
    return _push_sorted(g, src_val, active, out_init, kind, use_weight,
                        reverse)


def _push_sorted(g, src_val, active, out_init, kind, use_weight, reverse):
    """The jnp substrate's dense push as a reduction over destination-
    sorted edges: the CSR itself when reversed, else the CSC mirror, with a
    scatter over the CSR only where there is none.  On a TPU v5e, 64M
    messages into graph500-22's 4.2M vertices take 0.87 s as an unsorted
    scatter and 0.13 s as ``graph_ops.sorted_segment_reduce``.  Both relax
    the same message multiset, so min/max/or agree bitwise and add up to
    float association."""
    if reverse:
        return gk.pull_ref(g.col_idx, g.src_idx, g.edge_w, src_val, active,
                           out_init, kind, use_weight, g.row_ptr)
    if getattr(g, "has_csc", False):
        return gk.pull_ref(g.in_col_idx, g.in_src_idx, g.in_edge_w, src_val,
                           active, out_init, kind, use_weight, g.in_row_ptr)
    return gk.push_ref(g.src_idx, g.col_idx, g.edge_w, src_val, active,
                       out_init, kind, use_weight)


def pull_dense(
    g: Graph,
    src_val: jax.Array,
    active: jax.Array,
    out_init: jax.Array,
    kind: str = "min",
    use_weight: bool = True,
    substrate: str | None = None,
) -> jax.Array:
    """Pull-style relax over in-edges: each vertex reduces over its
    in-neighbours.  Requires CSC.  The jnp substrate reduces the
    destination-sorted in-edges with ``graph_ops.sorted_segment_reduce``;
    the Pallas substrate walks the same dst-sorted edge blocks."""
    sub = _resolve(substrate)
    tiered = getattr(g, "tiered_pull_dense", None)
    if tiered is not None:
        # out-of-core dispatch (core/tiered.py): stream the CSC mirror's
        # in-edge shards through the same bounded pool as the push path;
        # raises when the graph was cut without build_csc=True
        return tiered(src_val, active, out_init, kind, use_weight, sub,
                      det=(kind == "add" and _deterministic_add))
    if getattr(g, "is_tiered", False):
        raise NotImplementedError(
            "this tiered container holds only staged out-edge shards; "
            "pull runs on the TieredGraph itself (eager rounds), not "
            "inside a staged stretch")
    sharded = getattr(g, "sharded_pull_dense", None)
    if sharded is not None:
        if kind == "add" and _deterministic_add:
            return g.sharded_det_pull(src_val, active, out_init, use_weight)
        return sharded(src_val, active, out_init, kind, use_weight, sub)
    assert g.has_csc, "pull_dense requires build_csc=True"
    if kind == "add" and _deterministic_add:
        # pull ≡ push over the in-edge list (nbr → dst); same fixed order
        return gk.det_push_ref(g.in_col_idx, g.in_src_idx, g.in_edge_w,
                               src_val, active, out_init, use_weight)
    if sub == "pallas":
        return gk.edge_relax(
            g.in_col_idx, g.in_src_idx, g.in_edge_w, active, src_val,
            out_init, kind=kind, use_weight=use_weight, vertex_mask=True,
        )
    return gk.pull_ref(g.in_col_idx, g.in_src_idx, g.in_edge_w, src_val,
                       active, out_init, kind, use_weight, g.in_row_ptr)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EdgeBatch:
    """Result of a sparse advance: ``budget`` edge slots."""

    src: jax.Array     # (budget,) int32
    dst: jax.Array     # (budget,) int32
    w: jax.Array       # (budget,) float32
    valid: jax.Array   # (budget,) bool
    total: jax.Array   # () int32 — true number of frontier edges (overflow check)


def advance_sparse(
    g: Graph, f: SparseFrontier, budget: int, substrate: str | None = None
) -> EdgeBatch:
    """Merge-path expansion of a sparse frontier into ≤ budget edge slots.

    On a ``ShardedGraph`` the budget is **per shard**: every device expands
    the (replicated) frontier over its own edge shard, returning a
    ``ShardedEdgeBatch`` of (D, budget) slots.
    """
    sub = _resolve(substrate)
    sharded = getattr(g, "sharded_advance", None)
    if sharded is not None:
        return sharded(f, budget, sub)
    if sub == "pallas":
        src, dst, w, valid, total = gk.advance_frontier(
            f.idx, f.count, g.out_deg, g.row_ptr, g.col_idx, g.edge_w,
            budget=budget, sentinel=g.sentinel, m_pad=g.m_pad,
        )
    else:
        src, dst, w, valid, total = gk.advance_ref(
            f.idx, f.count, g.out_deg, g.row_ptr, g.col_idx, g.edge_w,
            budget=budget, sentinel=g.sentinel, m_pad=g.m_pad,
        )
    return EdgeBatch(src=src, dst=dst, w=w, valid=valid,
                     total=jnp.asarray(total, jnp.int32))


def relax_batch(
    batch: EdgeBatch,
    src_val: jax.Array,
    out_init: jax.Array,
    kind: str = "min",
    use_weight: bool = True,
    substrate: str | None = None,
) -> jax.Array:
    """Apply a relaxation over an EdgeBatch (sparse counterpart of push_dense)."""
    sub = _resolve(substrate)
    sharded = getattr(batch, "sharded_relax", None)
    if sharded is not None:
        if kind == "add" and _deterministic_add:
            return batch.sharded_det_relax(src_val, out_init, use_weight)
        return sharded(src_val, out_init, kind, use_weight, sub)
    if kind == "add" and _deterministic_add:
        return gk.det_relax_ref(batch.src, batch.dst, batch.w, batch.valid,
                                src_val, out_init, use_weight)
    if sub == "pallas":
        return gk.edge_relax(
            batch.src, batch.dst, batch.w, batch.valid, src_val, out_init,
            kind=kind, use_weight=use_weight, vertex_mask=False,
        )
    return gk.relax_ref(batch.src, batch.dst, batch.w, batch.valid, src_val,
                        out_init, kind, use_weight)


def batched_push_dense(
    g: Graph,
    src_val: jax.Array,
    active: jax.Array,
    out_init: jax.Array,
    kind: str = "min",
    use_weight: bool = True,
    substrate: str | None = None,
) -> jax.Array:
    """Multi-source ``push_dense``: relax every edge once for B lanes.

    ``src_val`` / ``active`` / ``out_init`` are (B, n_pad) lane matrices
    (row b = lane b's labels / frontier / accumulator).  The edge structure
    is fetched ONCE per sweep and amortized across all B lanes — the
    MS-BFS memory-traffic argument (core/multisource.py).  Per lane the
    result is bitwise equal to ``push_dense`` on that lane's row:

    * jnp     — ``batched_push_ref`` (axis-1 scatter, shared dst vector);
    * pallas  — ``jax.vmap`` of the blocked edge-relax kernel;
    * sharded — ``ShardedGraph.sharded_batched_push`` (lane-vmapped local
      relax + one full-mesh reduce of the (B, n_pad) accumulator);
    * det add — the canonical fixed-order tree, vmapped per lane.

    Tiered (out-of-core) graphs are not supported: serving batches run on
    resident or mesh-sharded graphs.
    """
    sub = _resolve(substrate)
    if getattr(g, "is_tiered", False):
        raise NotImplementedError(
            "batched multi-source relax needs the whole CSR resident "
            "(or mesh-sharded); the tiered streaming path is per-query")
    sharded = getattr(g, "sharded_batched_push", None)
    if sharded is not None:
        if kind == "add" and _deterministic_add:
            return g.sharded_batched_det_push(src_val, active, out_init,
                                              use_weight)
        return sharded(src_val, active, out_init, kind, use_weight, sub)
    if kind == "add" and _deterministic_add:
        return jax.vmap(
            lambda v, a, o: gk.det_push_ref(g.src_idx, g.col_idx, g.edge_w,
                                            v, a, o, use_weight)
        )(src_val, active, out_init)
    if sub == "pallas":
        return jax.vmap(
            lambda v, a, o: gk.edge_relax(
                g.src_idx, g.col_idx, g.edge_w, a, v, o,
                kind=kind, use_weight=use_weight, vertex_mask=True)
        )(src_val, active, out_init)
    if getattr(g, "has_csc", False):
        # destination-sorted in-edges, as in the single-lane dense push
        return jax.vmap(
            lambda v, a, o: gk.pull_ref(g.in_col_idx, g.in_src_idx,
                                        g.in_edge_w, v, a, o, kind,
                                        use_weight, g.in_row_ptr)
        )(src_val, active, out_init)
    return gk.batched_push_ref(g.src_idx, g.col_idx, g.edge_w, src_val,
                               active, out_init, kind, use_weight)


def batched_relax_batch(
    batch: EdgeBatch,
    src_val: jax.Array,
    active: jax.Array,
    out_init: jax.Array,
    kind: str = "min",
    use_weight: bool = True,
    substrate: str | None = None,
) -> jax.Array:
    """Multi-source ``relax_batch``: one sparse advance (over the lanes'
    *union* frontier) relaxed for B lanes at once.  A batch slot fires in
    lane b iff the slot is valid AND its source vertex is active in lane
    b's frontier row — which restores exactly lane b's message multiset,
    so each row is bitwise equal to the single-lane sparse round.  Plain
    ``EdgeBatch`` only (sharded batched rounds go through the dense
    sweep)."""
    sub = _resolve(substrate)
    assert not hasattr(batch, "sharded_relax"), \
        "batched sparse rounds are single-partition; sharded lanes relax dense"
    if kind == "add" and _deterministic_add:
        return jax.vmap(
            lambda m, v, o: gk.det_relax_ref(batch.src, batch.dst, batch.w,
                                             m, v, o, use_weight)
        )(batch.valid[None, :] & active[:, batch.src], src_val, out_init)
    if sub == "pallas":
        return jax.vmap(
            lambda m, v, o: gk.edge_relax(
                batch.src, batch.dst, batch.w, m, v, o,
                kind=kind, use_weight=use_weight, vertex_mask=False)
        )(batch.valid[None, :] & active[:, batch.src], src_val, out_init)
    return gk.batched_relax_ref(batch.src, batch.dst, batch.w, batch.valid,
                                src_val, active, out_init, kind, use_weight)


def batched_updated_mask(old: jax.Array, new: jax.Array) -> jax.Array:
    """Per-lane ``updated_mask``: (B, n_pad) rows of changed labels."""
    m = new != old
    return m.at[:, -1].set(False)  # sentinel never activates


def relax_edges(
    g: Graph,
    src_val: jax.Array,
    edge_mask: jax.Array,
    out_init: jax.Array,
    kind: str = "min",
    use_weight: bool = True,
    substrate: str | None = None,
) -> jax.Array:
    """Relax the graph's full out-edge list under a **per-edge** validity
    mask — for algorithms whose activation is a property of the edge, not
    the source vertex (delta-stepping's light/heavy split).  ``edge_mask``
    is (m_pad,)-aligned with the flat edge views; on a ``ShardedGraph`` it
    is resharded with the edges and the relax runs shard-local + cross-
    device reduce like every other operator."""
    sub = _resolve(substrate)
    sharded = getattr(g, "sharded_relax_edges", None)
    if sharded is not None:
        if kind == "add" and _deterministic_add:
            return g.sharded_det_relax_edges(src_val, edge_mask, out_init,
                                             use_weight)
        return sharded(src_val, edge_mask, out_init, kind, use_weight, sub)
    if kind == "add" and _deterministic_add:
        return gk.det_relax_ref(g.src_idx, g.col_idx, g.edge_w, edge_mask,
                                src_val, out_init, use_weight)
    if sub == "pallas":
        return gk.edge_relax(
            g.src_idx, g.col_idx, g.edge_w, edge_mask, src_val, out_init,
            kind=kind, use_weight=use_weight, vertex_mask=False,
        )
    return gk.relax_ref(g.src_idx, g.col_idx, g.edge_w, edge_mask, src_val,
                        out_init, kind, use_weight)


def intersect_batch(
    adj: jax.Array,
    src: jax.Array,
    dst: jax.Array,
    *,
    sentinel: int,
    substrate: str | None = None,
) -> jax.Array:
    """Oriented sorted-intersection count for a batch of oriented edges —
    triangle counting's operator (tc's chunked loop and its sharded
    edge-chunk dispatch both lower through this seam).

    ``adj`` is the (n_pad, dmax) sorted oriented adjacency (sentinel-padded
    rows; ``adj[sentinel]`` all-sentinel), ``src``/``dst`` the oriented
    edge endpoints (sentinel on padding slots).  Returns the exact int32
    sum of |N+(src_i) ∩ N+(dst_i)| — bitwise identical across substrates,
    chunk sizes and shard partitions (integer reduction)."""
    sub = _resolve(substrate)
    if sub == "pallas":
        return gk.intersect_count(adj, src, dst, sentinel=sentinel)
    return gk.intersect_ref(adj, src, dst, sentinel)


def sparse_round(
    g: Graph,
    src_val: jax.Array,
    mask: jax.Array,
    out_init: jax.Array,
    kind: str = "min",
    use_weight: bool = True,
    *,
    capacity: int,
    budget: int,
    substrate: str | None = None,
):
    """One fused data-driven round: compact → advance → relax.

    On a plain ``Graph`` this composes the existing ops (global compaction
    into a ``capacity``-slot worklist, merge-path advance into ``budget``
    edge slots, batch relax).  On a ``ShardedGraph`` the whole round runs
    *inside* ``shard_map`` — per-shard compaction over locally-present
    vertices, per-shard overflow detection, and per-shard escalation to a
    shard-local dense relax when a hub-heavy shard outgrows the rung (see
    ``ShardedGraph.sharded_sparse_round``).

    Returns ``(new_out, escalated_shards)`` — the escalation count is 0 on
    a single partition, and the number of shards that fell back to their
    local dense relax on a mesh (labels are bitwise identical either way).

    The whole round — both dispatch targets included — is
    ``lax.while_loop``-body safe: pure device computation, statically
    shaped, no host fetch, with the escalation count returned as a device
    int32 (never forced to a Python int here).  The fused engine relies on
    this to run consecutive same-rung rounds device-resident, carrying the
    escalation counter in the loop carry (``engine._sparse_stretch``).
    """
    sub = _resolve(substrate)
    if getattr(g, "is_tiered", False):
        # shard-granular work efficiency: the masked push already streams
        # only the shards the frontier's vertices live in, which IS the
        # sparse round's point on a tiered graph — compaction into a
        # worklist would buy nothing, the bandwidth saving comes from the
        # shards never fetched
        out = push_dense(g, src_val, mask, out_init, kind, use_weight, sub)
        return out, jnp.int32(0)
    fused = getattr(g, "sharded_sparse_round", None)
    if fused is not None:
        if kind == "add" and _deterministic_add:
            # deterministic float-add wants the one canonical edge order;
            # a masked dense push over all local edges relaxes the same
            # message set as the sparse round, with no overflow to manage
            out = push_dense(g, src_val, mask, out_init, kind, use_weight,
                             sub)
            return out, jnp.int32(0)
        return fused(src_val, mask, out_init, kind, use_weight, capacity,
                     budget, sub)
    f = fr.compact(mask, capacity, g.sentinel)
    batch = advance_sparse(g, f, budget, sub)
    out = relax_batch(batch, src_val, out_init, kind, use_weight, sub)
    return out, jnp.int32(0)


def direction_choice(
    g: Graph,
    frontier_edges: jax.Array,
    unvisited_edges: jax.Array,
    frontier_count: jax.Array,
    currently_pull: jax.Array,
    alpha: float = 14.0,
    beta: float = 24.0,
) -> jax.Array:
    """Beamer's direction-optimizing heuristic.

    Switch push→pull when the frontier's out-edge mass exceeds
    ``unvisited_edges / alpha``; switch pull→push when the frontier shrinks
    below ``n / beta`` vertices.  Returns True for "pull this round".
    """
    go_pull = frontier_edges > unvisited_edges / alpha
    go_push = frontier_count < g.n / beta
    return jnp.where(currently_pull, ~go_push, go_pull)


def updated_mask(old: jax.Array, new: jax.Array) -> jax.Array:
    m = new != old
    return m.at[-1].set(False)  # sentinel never activates
