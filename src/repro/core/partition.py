"""Distributed graph partitions + the BSP vertex-program engine.

This is the paper's *comparison baseline* (D-Galois on Stampede2, §6.3),
built in-framework so benchmarks can reproduce Fig. 11 on one host:

* ``partition_1d`` — Outgoing Edge Cut (OEC): contiguous vertex ranges per
  device; each device owns the out-edges of its vertices (the paper uses OEC
  for 5–20 hosts).
* ``partition_2d`` — Cartesian Vertex Cut (CVC): the device grid (R, C) tiles
  the adjacency matrix; device (i, j) owns edges with src in row-block i and
  dst in column-block j (the paper's choice for 256 hosts).  Communication
  for a round is an all-gather of source labels along grid rows and a
  min/sum-reduction of destination updates along grid columns — the
  communication-avoiding structure that makes CVC scale.

The BSP engine (``bsp_round``) runs a bulk-synchronous vertex-program round
under ``shard_map``: local edge relaxation into a label-width accumulator,
then a cross-device reduction (Gluon-style sync).  It supports only dense
worklists and vertex operators — exactly the restriction the paper points
out for distributed frameworks; benchmarks exploit that contrast.

Edge shards are padded to equal length per device (SPMD static shapes).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .graph import Graph, round_up
from . import operators as ops
from . import placement as pl


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PartitionedGraph:
    """Edge-partitioned graph: (D, epd) edge arrays, device-major.

    Each shard's edge slots are kept in shard-local CSR order (sorted by
    ``(src, dst)``), and ``row_ptr``/``deg`` carry the shard-local CSR
    offsets and per-vertex degrees over *global* vertex ids.  The BSP engine
    below ignores them; the sharded operator path (``core/sharded.py``)
    needs them so each device can merge-path-expand a sparse frontier over
    its own edges — the shard metadata is the open interface, not the
    closed BSP step.  (For ``direction="in"`` partitions the CSR metadata
    is keyed by the in-neighbour and only the flat edge lists are used.)
    """

    n: int = dataclasses.field(metadata=dict(static=True))
    n_pad: int = dataclasses.field(metadata=dict(static=True))
    ndev: int = dataclasses.field(metadata=dict(static=True))
    epd: int = dataclasses.field(metadata=dict(static=True))  # edges per device
    scheme: str = dataclasses.field(metadata=dict(static=True))  # "oec" | "cvc"
    policy: str = dataclasses.field(metadata=dict(static=True))  # shard homing

    src: jax.Array     # (D, epd) int32, sentinel-padded, shard-local CSR order
    dst: jax.Array     # (D, epd)
    w: jax.Array       # (D, epd)
    out_deg: jax.Array  # (n_pad,) global out-degrees (replicated)
    row_ptr: jax.Array  # (D, n_pad + 1) shard-local CSR offsets
    deg: jax.Array      # (D, n_pad) shard-local per-vertex degree

    # reduce-side ownership metadata (drives sharded.CrossReducer): the
    # device grid is (rows, cols) — (ndev, 1) for 1-D cuts — and
    # ``reduce_owner`` maps each vertex to the owner along the reduce
    # dimension (grid column for CVC, the whole device axis for OEC).  The
    # partition invariant: every edge's accumulator target (dst for "out",
    # destination for "in") lands on a shard whose reduce-dimension index
    # equals ``reduce_owner[target]``.
    rows: int = dataclasses.field(default=0, metadata=dict(static=True))
    cols: int = dataclasses.field(default=0, metadata=dict(static=True))
    reduce_owner: jax.Array = None  # (n_pad,) int32

    @property
    def sentinel(self) -> int:
        return self.n_pad - 1


def _assemble(shards, n, n_pad, out_deg, scheme, policy, rows, cols,
              reduce_owner) -> PartitionedGraph:
    ndev = len(shards)
    sentinel = n_pad - 1
    epd = round_up(max(max(len(s[0]) for s in shards), 1), 8)
    S = np.full((ndev, epd), sentinel, np.int32)
    D = np.full((ndev, epd), sentinel, np.int32)
    W = np.zeros((ndev, epd), np.float32)
    RP = np.zeros((ndev, n_pad + 1), np.int32)
    DEG = np.zeros((ndev, n_pad), np.int32)
    for i, (s, d, w) in enumerate(shards):
        # shard-local CSR order: (s, d)-sorted, as one int64 key
        order = np.argsort(s.astype(np.int64) * n_pad + d, kind="stable")
        s, d, w = s[order], d[order], w[order]
        S[i, : len(s)] = s
        D[i, : len(d)] = d
        W[i, : len(w)] = w
        counts = np.bincount(s, minlength=n_pad).astype(np.int32)
        counts[sentinel] = 0
        DEG[i] = counts
        np.cumsum(counts, out=RP[i, 1:])
    return PartitionedGraph(
        n=n, n_pad=n_pad, ndev=ndev, epd=epd, scheme=scheme, policy=policy,
        src=jnp.asarray(S), dst=jnp.asarray(D), w=jnp.asarray(W),
        out_deg=jnp.asarray(out_deg),
        row_ptr=jnp.asarray(RP), deg=jnp.asarray(DEG),
        rows=rows, cols=cols,
        reduce_owner=jnp.asarray(reduce_owner.astype(np.int32)),
    )


def _edge_arrays(g: Graph, direction: str):
    if direction == "in":
        assert g.has_csc, "direction='in' requires build_csc=True"
        # in-edge list: (in-neighbour, destination, weight); owner-computes
        # homes an in-edge with its *destination*
        return (np.asarray(g.in_col_idx)[: g.m], np.asarray(g.in_src_idx)[: g.m],
                np.asarray(g.in_edge_w)[: g.m], np.asarray(g.in_src_idx)[: g.m])
    src = np.asarray(g.src_idx)[: g.m]
    return src, np.asarray(g.col_idx)[: g.m], np.asarray(g.edge_w)[: g.m], src


def partition_1d(
    g: Graph, ndev: int, policy: str = "blocked", direction: str = "out"
) -> PartitionedGraph:
    """1-D edge cut: device owns the out-edges of its vertex range (OEC; the
    paper uses it for 5–20 hosts).  ``policy`` picks the placement.py homing
    rule (blocked ranges / interleaved blocks / all-local); ``direction="in"``
    cuts the CSC in-edge list by destination instead (pull direction)."""
    src, dst, w, own_key = _edge_arrays(g, direction)
    owner = pl.shard_owner(own_key, g.n_pad, g.block_size, ndev, policy)
    shards = [
        (src[owner == i], dst[owner == i], w[owner == i]) for i in range(ndev)
    ]
    red_owner = pl.vertex_owner(g.n_pad, g.block_size, ndev, policy)
    return _assemble(shards, g.n, g.n_pad, np.asarray(g.out_deg), "oec",
                     policy, ndev, 1, red_owner)


def partition_2d(
    g: Graph, rows: int, cols: int, policy: str = "blocked",
    direction: str = "out"
) -> PartitionedGraph:
    """CVC on an (rows, cols) grid, flattened device-major (row*cols + col).

    The grid row is keyed on the gather side of the relaxation (src for
    ``direction="out"``, the in-neighbour for ``direction="in"``) and the
    grid *column* on the scatter side (the accumulator target), so every
    shard's updates land on vertices its own grid column owns — the
    invariant the communication-avoiding reducer reduces along columns on.
    """
    src, dst, w, _ = _edge_arrays(g, direction)
    r = pl.shard_owner(src, g.n_pad, g.block_size, rows, policy)
    c = pl.shard_owner(dst, g.n_pad, g.block_size, cols, policy)
    owner = r * cols + c
    shards = [
        (src[owner == i], dst[owner == i], w[owner == i])
        for i in range(rows * cols)
    ]
    red_owner = pl.vertex_owner(g.n_pad, g.block_size, cols, policy)
    return _assemble(shards, g.n, g.n_pad, np.asarray(g.out_deg), "cvc",
                     policy, rows, cols, red_owner)


# ---------------------------------------------------------------------------
# BSP vertex-program engine (the D-Galois analogue)
# ---------------------------------------------------------------------------

def make_bsp_step(
    pg: PartitionedGraph,
    mesh: Mesh,
    axes: Tuple[str, ...],
    kind: str = "min",
    use_weight: bool = True,
):
    """Returns a jitted BSP round: (labels, mask) -> (labels, mask).

    labels/mask are replicated; edge shards live one-per-device.  The sync is
    a full cross-device reduction of the label vector (dense Gluon sync) —
    communication O(n) per round, the cost the paper's Fig. 11 charges the
    cluster for.
    """
    def local_round(labels, mask, src, dst, w):
        # src/dst/w: (1, epd) local shard (leading device dim of size 1 each)
        src, dst, w = src[0], dst[0], w[0]
        v = labels[src]
        if kind in ("min", "max"):
            msg = v + w if use_weight else v
        else:
            msg = v * w if use_weight else v
        neutral = ops.neutral_for(kind, labels.dtype)
        msg = jnp.where(mask[src], msg.astype(labels.dtype), neutral)
        acc = ops.scatter_reduce(dst, msg, jnp.full_like(labels, neutral), kind)
        # Gluon-style reduce of mirrors → canonical labels on every device
        if kind == "min":
            acc = jax.lax.pmin(acc, axes)
            new = jnp.minimum(labels, acc)
        elif kind == "max":
            acc = jax.lax.pmax(acc, axes)
            new = jnp.maximum(labels, acc)
        else:
            acc = jax.lax.psum(acc, axes)
            new = labels + acc
        return new, ops.updated_mask(labels, new)

    smapped = jax.shard_map(
        local_round,
        mesh=mesh,
        in_specs=(P(), P(), P(axes), P(axes), P(axes)),
        out_specs=(P(), P()),
        check_vma=False,
    )

    @jax.jit
    def step(labels, mask, src, dst, w):
        return smapped(labels, mask, src, dst, w)

    def run(labels, mask):
        return step(labels, mask, pg.src, pg.dst, pg.w)

    return run


def bsp_bfs(pg: PartitionedGraph, mesh: Mesh, axes, src_vertex: int,
            max_rounds: int = 100_000):
    """Distributed BFS as a bulk-synchronous vertex program (dense worklist)."""
    INF = jnp.float32(jnp.finfo(jnp.float32).max / 4)
    labels = jnp.full((pg.n_pad,), INF).at[src_vertex].set(0.0)
    mask = jnp.zeros((pg.n_pad,), bool).at[src_vertex].set(True)
    step = make_bsp_step(pg, mesh, axes, kind="min", use_weight=True)
    rounds = 0
    while bool(jnp.any(mask)) and rounds < max_rounds:
        labels, mask = step(labels, mask)
        rounds += 1
    return labels, rounds


def bsp_cc(pg: PartitionedGraph, mesh: Mesh, axes, max_rounds: int = 100_000):
    """Distributed label-propagation CC — the vertex-program-only algorithm a
    distributed framework is restricted to (no pointer jumping across hosts)."""
    labels = jnp.arange(pg.n_pad, dtype=jnp.int32)
    mask = jnp.ones((pg.n_pad,), bool).at[pg.n_pad - 1].set(False)
    step = make_bsp_step(pg, mesh, axes, kind="min", use_weight=False)
    rounds = 0
    while bool(jnp.any(mask)) and rounds < max_rounds:
        labels, mask = step(labels, mask)
        rounds += 1
    return labels, rounds
