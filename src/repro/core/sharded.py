"""Sharded execution path for the single-machine engine.

``shard_graph`` turns a :class:`~repro.core.graph.Graph` into a
:class:`ShardedGraph`: per-device edge shards produced by
``partition_1d``/``partition_2d``, homed by a ``placement.py`` policy
(``local`` / ``interleaved`` / ``blocked``), plus the shard-local CSR
metadata the sparse operators need.  ``core.operators`` dispatches
``push_dense`` / ``pull_dense`` / ``advance_sparse`` / ``relax_batch`` /
``sparse_round`` to the methods here whenever it is handed a
``ShardedGraph``, so ``SparseLadderEngine`` and ``run_dense`` — **including
sparse worklists and per-shard merge-path budgets, which the BSP baseline
cannot express** — run unmodified on a D-device mesh.

Every sharded relaxation has the same three-phase structure:

1. **shard-local relax** through the selected substrate (jnp reference ops
   or the Pallas kernels — the same kernel seam as the single-device path)
   into a neutral-initialised accumulator;
2. **cross-device label reduction** through a :class:`CrossReducer` keyed
   on the partition structure (the communication-avoiding piece, see
   below);
3. **merge** with the caller's ``out_init``, reusing the reduction-kind
   semantics of ``kernels.graph_ops.scatter_reduce``.

Cross-device reduction structure
--------------------------------

The PR 2 path reduced every per-shard accumulator with a full
``pmin``/``psum`` over *all* mesh axes — O(D·N) reduction volume whatever
the partition shape.  :class:`CrossReducer` replaces that with the
communication-avoiding structure of the partition (Gluon's CVC sync at 256
hosts, mapped to the mesh):

* ``"cvc2d"`` — for ``partition_2d`` grids on a 2-axis mesh: device (i, j)
  only produces updates for vertices its grid *column* j owns (the
  partition invariant), so the reduction runs along the mesh **column
  groups only** (each an R-device reduce of the column's owned slice), and
  the reduced owned slices are then all-gathered along the mesh **rows**
  to rebuild the replicated label vector for the next relax.
* ``"owner1d"`` — for ``partition_1d``: an owner-targeted
  ``psum_scatter``-style reduce.  Each device re-orders its accumulator
  into the per-owner layout (``placement.owner_layout``), an ``all_to_all``
  hands every owner exactly the contributions to *its* vertices, the owner
  combines them once, and an ``all_gather`` of the combined owned slices
  rebuilds the replicated vector — every reduced element is computed once
  instead of D times.
* ``"full"`` — the PR 2 full-mesh reduce, kept as the comparison baseline
  (``shard_graph(..., reducer="full")``; ``benchmarks/comm_volume.py``
  sweeps it against the communication-avoiding modes).

``min`` / ``max`` / ``or`` reductions are order-independent, so every
reducer mode is **bitwise identical** to the single-device jnp reference
for any (substrate, placement, ndev) cell — ``tests/test_sharded_invariance``
pins the full matrix, CVC against full-mesh included.  Plain float ``add``
still depends on the partition; under
``operators.set_deterministic_add(True)`` the sharded ``add`` path instead
re-orders the flat edge multiset into one canonical (src, dst, w) order and
runs the fixed-order segmented tree (``graph_ops.det_scatter_add``) on it,
which makes sharded float sums bitwise identical across *every* (placement,
ndev) cell — and identical to the single-device deterministic path, since
``from_coo``'s CSR layout sorts edges the same way.

Communication accounting
------------------------

``CrossReducer.comm_per_relax`` is the analytic model the engines feed into
``RunStats.comm_elems`` / ``comm_bytes`` / ``reduce_axis_hops``: every
collective over a K-device group with a per-member payload of L elements is
charged K·(K−1)·L element-hops (the mirror-exchange volume of a dense
Gluon-style sync — the same convention for every mode, so ratios are
meaningful).  ``benchmarks/comm_volume.py`` and ``benchmarks/scaling.py``
sweep it CVC-vs-full-mesh across device counts.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels import graph_ops as gk
from .frontier import SparseFrontier, compact_local
from .graph import Graph
from .partition import PartitionedGraph, partition_1d, partition_2d
from . import placement as pl


def _local_relax(src, dst, w, mask, src_val, neutral_init, kind, use_weight,
                 vertex_mask, substrate):
    """One shard's relaxation through the substrate seam (PR 1 kernels)."""
    if substrate == "pallas":
        return gk.edge_relax(src, dst, w, mask, src_val, neutral_init,
                             kind=kind, use_weight=use_weight,
                             vertex_mask=vertex_mask)
    if vertex_mask:
        return gk.push_ref(src, dst, w, src_val, mask, neutral_init, kind,
                           use_weight)
    return gk.relax_ref(src, dst, w, mask, src_val, neutral_init, kind,
                        use_weight)


def _cross_reduce(acc, axes, kind):
    """Full-mesh reduce of per-shard accumulators (the PR 2 baseline)."""
    if kind == "min":
        return jax.lax.pmin(acc, axes)
    if kind == "max":
        return jax.lax.pmax(acc, axes)
    if kind == "or":
        if acc.dtype == jnp.bool_:
            return jax.lax.pmax(acc.astype(jnp.uint8), axes).astype(bool)
        return jax.lax.pmax(acc, axes)
    if kind == "add":
        return jax.lax.psum(acc, axes)
    raise ValueError(kind)


def _merge(out_init, acc, kind):
    """Fold the reduced accumulator into the caller's out_init — the same
    merge ``scatter_reduce`` performs on a single device."""
    if kind == "min":
        return jnp.minimum(out_init, acc)
    if kind == "max":
        return jnp.maximum(out_init, acc)
    if kind == "or":
        if out_init.dtype == jnp.bool_:
            return out_init | acc
        return jnp.maximum(out_init, acc.astype(out_init.dtype))
    if kind == "add":
        return out_init + acc
    raise ValueError(kind)


def _combine_rows(stack, kind):
    """Reduce a (K, L) stack of per-device contributions along axis 0."""
    if kind == "min":
        return jnp.min(stack, axis=0)
    if kind in ("max", "or"):
        return jnp.max(stack, axis=0)
    if kind == "add":
        return jnp.sum(stack, axis=0)
    raise ValueError(kind)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CrossReducer:
    """Cross-device label-reduction strategy, keyed on partition structure.

    ``mode`` is one of ``"full"`` (all-axis all-reduce, the PR 2 baseline),
    ``"cvc2d"`` (column-group reduce + row gather over a (rows, cols)
    grid), ``"owner1d"`` (owner-targeted all_to_all reduce-scatter +
    gather).  ``own_idx``/``own_valid`` are the ``placement.owner_layout``
    of the reduce-side ownership map (None for ``"full"``): row k lists the
    vertices owned by reduce-group k, sentinel-padded to a rectangle.
    """

    mode: str = dataclasses.field(metadata=dict(static=True))
    axes: Tuple[str, ...] = dataclasses.field(metadata=dict(static=True))
    rows: int = dataclasses.field(metadata=dict(static=True))
    cols: int = dataclasses.field(metadata=dict(static=True))
    own_idx: Optional[jax.Array] = None    # (groups, L) int32
    own_valid: Optional[jax.Array] = None  # (groups, L) bool

    @property
    def ndev(self) -> int:
        return self.rows * self.cols

    def _scatter_back(self, gathered, valid, kind, n_pad, dtype):
        """Rebuild the replicated (n_pad,) vector from gathered owned
        slices.  Valid entries tile the vertex range exactly once (the
        owner-map contract); padding slots all point at the sentinel and
        carry the neutral, which the kind-reduce absorbs."""
        neutral = gk.neutral_for(kind, dtype)
        vals = jnp.where(valid.reshape(-1), gathered.reshape(-1), neutral)
        out = jnp.full((n_pad,), neutral, dtype)
        return gk.scatter_reduce(self.own_idx.reshape(-1), vals, out, kind)

    def reduce(self, acc, kind):
        """Reduce per-shard accumulators to canonical labels on every
        device.  Must be called inside ``shard_map`` over ``self.axes``."""
        if self.mode == "full" or self.ndev == 1:
            return _cross_reduce(acc, self.axes, kind)
        widened = acc.dtype == jnp.bool_
        work = acc.astype(jnp.uint8) if widened else acc
        if self.mode == "cvc2d":
            r_ax, c_ax = self.axes
            j = jax.lax.axis_index(c_ax)
            idx = jnp.take(self.own_idx, j, axis=0)        # (L,) column slice
            part = work[idx]
            # reduce along the grid column group only: the R devices of
            # column j hold every contribution to j's owned vertices.  The
            # widened (bool→uint8) accumulator keeps the caller's kind:
            # _cross_reduce already maps "or" to pmax on uint8, and a bool
            # "min" (AND) must stay pmin — substituting max here would
            # silently compute OR for it
            red = _cross_reduce(part, (r_ax,), kind)
            # rebuild the replicated vector: gather owned slices along rows
            gat = jax.lax.all_gather(red, c_ax)            # (C, L)
            out = self._scatter_back(gat, self.own_valid, kind, acc.shape[0],
                                     work.dtype)
        else:  # owner1d
            (ax,) = self.axes
            D, L = self.own_idx.shape
            # per-owner layout of my contributions; chunk k goes to owner k
            contrib = work[self.own_idx.reshape(-1)].reshape(D, L)
            swapped = jax.lax.all_to_all(contrib, ax, split_axis=0,
                                         concat_axis=0, tiled=True)
            # owner combines the D incoming chunks once (reduce-scatter)
            red = _combine_rows(swapped.reshape(D, L), kind)
            gat = jax.lax.all_gather(red, ax)              # (D, L)
            out = self._scatter_back(gat, self.own_valid, kind, acc.shape[0],
                                     work.dtype)
        return out.astype(bool) if widened else out

    def comm_per_relax(self, n_pad: int, itemsize: int = 4):
        """Analytic cross-device traffic of ONE dense label reduction:
        ``(elems, bytes, axis_hops)``.

        Every collective over a K-device group with per-member payload L is
        charged K·(K−1)·L element-hops — the mirror-exchange volume of a
        dense Gluon-style sync, applied uniformly to every mode so the
        CVC-vs-full ratios are apples-to-apples.  ``axis_hops`` counts mesh
        axes traversed by the *reduction* (the gather is rebuild traffic).
        """
        D = self.ndev
        if D <= 1:
            return 0, 0, 0
        if self.mode == "full":
            elems = D * (D - 1) * n_pad
            return elems, elems * itemsize, len(self.axes)
        L = int(self.own_idx.shape[1])
        if self.mode == "cvc2d":
            reduce_elems = self.cols * self.rows * (self.rows - 1) * L
            gather_elems = self.rows * self.cols * (self.cols - 1) * L
        else:  # owner1d: all_to_all + all_gather, both over the full axis
            reduce_elems = D * (D - 1) * L
            gather_elems = D * (D - 1) * L
        elems = reduce_elems + gather_elems
        return elems, elems * itemsize, 1


def _edge_scatter(mesh, axes, red, e_src, e_dst, e_w, src_val, mask, out_init,
                  kind, use_weight, substrate, vertex_mask=True):
    """shard_map a relaxation over (D, epd) edge shards.

    ``mask`` is the replicated (n_pad,) active-vertex bitmap when
    ``vertex_mask``, else a per-edge (D, epd) validity mask sharded with
    the edges.
    """
    neutral = gk.neutral_for(kind, out_init.dtype)

    def local(vals, msk, out0, s, d, w):
        s, d, w = s[0], d[0], w[0]
        m = msk if vertex_mask else msk[0]
        acc = _local_relax(s, d, w, m, vals, jnp.full_like(out0, neutral),
                           kind, use_weight, vertex_mask, substrate)
        return _merge(out0, red.reduce(acc, kind), kind)

    mask_spec = P() if vertex_mask else P(axes)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), mask_spec, P(), P(axes), P(axes), P(axes)),
        out_specs=P(), check_vma=False,
    )
    return fn(src_val, mask, out_init, e_src, e_dst, e_w)


def _det_add_flat(src, dst, w, src_val, out_init, use_weight,
                  active=None, valid=None):
    """Sharded ``kind="add"`` under deterministic mode: canonical-order
    fixed-tree reduction over the *flat* edge multiset.

    The flat shard views concatenate edges in partition order, which
    differs per (placement, ndev) — so the arrays are first re-ordered
    into the canonical (src, dst, w) order, which is a pure function of
    the edge multiset.  ``det_scatter_add`` then stable-sorts by dst, so
    the final association order matches the single-device deterministic
    path exactly (``from_coo`` lays edges out (src, dst)-sorted): sharded
    float sums are bitwise identical across every placement × ndev cell
    *and* to the unsharded deterministic result.

    Caveat (same as the ROADMAP's): duplicate (src, dst) pairs with
    different weights tie-break by weight here but by input position in
    ``from_coo``'s layout, so the unsharded-identity claim holds for
    deduplicated graphs — ``from_coo(dedup=True)``, the default, removes
    such multi-edges.
    """
    order = jnp.lexsort((w, dst, src))
    s, d, ww = src[order], dst[order], w[order]
    if valid is not None:
        v = valid[order]
        return gk.det_relax_ref(s, d, ww, v, src_val, out_init, use_weight)
    return gk.det_push_ref(s, d, ww, src_val, active, out_init, use_weight)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedEdgeBatch:
    """Sparse advance result on a mesh: ``budget`` edge slots *per shard*.

    ``totals`` is per-shard true frontier edge mass; ``total`` (the global
    overflow check, mirroring ``EdgeBatch.total``) is their sum.
    """

    mesh: Mesh = dataclasses.field(metadata=dict(static=True))
    axes: Tuple[str, ...] = dataclasses.field(metadata=dict(static=True))
    src: jax.Array      # (D, budget) int32
    dst: jax.Array      # (D, budget)
    w: jax.Array        # (D, budget)
    valid: jax.Array    # (D, budget) bool
    totals: jax.Array   # (D,) int32
    red: Optional[CrossReducer] = None

    @property
    def total(self) -> jax.Array:
        return jnp.sum(self.totals).astype(jnp.int32)

    def _reducer(self) -> CrossReducer:
        if self.red is not None:
            return self.red
        return CrossReducer(mode="full", axes=self.axes,
                            rows=_num_devices(self.mesh, self.axes), cols=1)

    def sharded_relax(self, src_val, out_init, kind, use_weight, substrate):
        return _edge_scatter(self.mesh, self.axes, self._reducer(), self.src,
                             self.dst, self.w, src_val, self.valid, out_init,
                             kind, use_weight, substrate, vertex_mask=False)

    def sharded_det_relax(self, src_val, out_init, use_weight):
        """Deterministic ``add`` over the batch: canonical-order fixed tree
        on the flat slots.  The expanded edge multiset (union over shards)
        is partition-independent — padding slots carry exact zeros — so
        the sums are bitwise stable across placement × ndev."""
        return _det_add_flat(self.src.reshape(-1), self.dst.reshape(-1),
                             self.w.reshape(-1), src_val, out_init,
                             use_weight, valid=self.valid.reshape(-1))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedGraph:
    """Edge-sharded graph that quacks like ``Graph`` for the engines.

    Carries (D, epd) edge shards in shard-local CSR order plus per-shard
    CSR metadata (``shard_row_ptr``/``shard_deg`` over global vertex ids),
    so each device can expand a sparse frontier over its own edges.  Vertex
    arrays (labels, degrees, masks) stay replicated — they are the lookup
    side of the gathers, same rule as ``placement.place_graph``.  ``red``
    is the :class:`CrossReducer` every relaxation's phase-2 reduction runs
    through.
    """

    # static metadata
    n: int = dataclasses.field(metadata=dict(static=True))
    m: int = dataclasses.field(metadata=dict(static=True))
    n_pad: int = dataclasses.field(metadata=dict(static=True))
    block_size: int = dataclasses.field(metadata=dict(static=True))
    ndev: int = dataclasses.field(metadata=dict(static=True))
    epd: int = dataclasses.field(metadata=dict(static=True))
    scheme: str = dataclasses.field(metadata=dict(static=True))
    placement: str = dataclasses.field(metadata=dict(static=True))
    axes: Tuple[str, ...] = dataclasses.field(metadata=dict(static=True))
    mesh: Mesh = dataclasses.field(metadata=dict(static=True))

    # CSR out-edge shards (push direction / sparse advance)
    src: jax.Array            # (D, epd) int32, sentinel-padded
    dst: jax.Array            # (D, epd)
    w: jax.Array              # (D, epd)
    shard_row_ptr: jax.Array  # (D, n_pad + 1)
    shard_deg: jax.Array      # (D, n_pad)
    out_deg: jax.Array        # (n_pad,) global (replicated)

    # in-edge shards (pull direction) — optional
    in_nbr: Optional[jax.Array] = None   # (D, epd_in) in-neighbour
    in_dst: Optional[jax.Array] = None   # (D, epd_in) destination
    in_w: Optional[jax.Array] = None     # (D, epd_in)

    # cross-device reduction strategy (None degrades to full-mesh)
    red: Optional[CrossReducer] = None

    # ---- Graph-compatible surface -------------------------------------
    @property
    def sentinel(self) -> int:
        return self.n_pad - 1

    @property
    def m_pad(self) -> int:
        return self.ndev * self.epd

    @property
    def has_csc(self) -> bool:
        return self.in_nbr is not None

    def vertex_full(self, fill, dtype) -> jax.Array:
        return jnp.full((self.n_pad,), fill, dtype=dtype)

    def valid_vertex_mask(self) -> jax.Array:
        return jnp.arange(self.n_pad) < self.n

    # flat views so non-operator algorithms (pointer-jump CC, delta-stepping)
    # run unmodified: the concatenated shards are the same edge multiset as
    # the original CSR arrays, sentinel-padded per shard
    @property
    def src_idx(self) -> jax.Array:
        return self.src.reshape(-1)

    @property
    def col_idx(self) -> jax.Array:
        return self.dst.reshape(-1)

    @property
    def edge_w(self) -> jax.Array:
        return self.w.reshape(-1)

    def _reducer(self) -> CrossReducer:
        if self.red is not None:
            return self.red
        return CrossReducer(mode="full", axes=self.axes, rows=self.ndev,
                            cols=1)

    def comm_per_relax(self, itemsize: int = 4, reverse: bool = False):
        """Analytic (elems, bytes, reduce-axis hops) of one cross-device
        label reduction on this graph — what the engines accumulate into
        ``RunStats``.  ``reverse=True`` models a reversed edge scatter,
        which executes through the reverse-safe reducer (cvc2d degrades to
        full-mesh), so bc's backward sweeps are charged what they actually
        cost.  (The opt-in deterministic-add path replicates flat edge
        views instead of reducing; the model does not special-case it.)"""
        red = self._reverse_safe_reducer() if reverse else self._reducer()
        return red.comm_per_relax(self.n_pad, itemsize)

    def budget_edge_mass(self, mask: jax.Array) -> jax.Array:
        """Max *per-shard* frontier edge mass — what a per-shard merge-path
        budget must cover (the global mass is what a single device needs)."""
        per = jnp.sum(jnp.where(mask[None, :], self.shard_deg, 0), axis=1)
        return jnp.max(per)

    def _reverse_safe_reducer(self) -> CrossReducer:
        """Reducer for a *reversed* edge scatter (updates land on edge
        sources).  The CVC 2-D structure relies on every update hitting a
        vertex the device's grid column owns — reversed scatters hit the
        row side instead, so cvc2d would silently drop cross-column
        contributions; degrade that one mode to the full-mesh reduce.
        owner1d is a full reduce-scatter over the whole vector (correct
        for any production pattern) and is kept."""
        red = self._reducer()
        if red.mode == "cvc2d":
            return CrossReducer(mode="full", axes=red.axes, rows=red.rows,
                                cols=red.cols)
        return red

    # ---- sharded operator implementations (operators.py dispatch) -----
    def sharded_push_dense(self, src_val, active, out_init, kind, use_weight,
                           substrate, reverse=False):
        if reverse:
            return _edge_scatter(self.mesh, self.axes,
                                 self._reverse_safe_reducer(), self.dst,
                                 self.src, self.w, src_val, active, out_init,
                                 kind, use_weight, substrate,
                                 vertex_mask=True)
        return _edge_scatter(self.mesh, self.axes, self._reducer(), self.src,
                             self.dst, self.w, src_val, active, out_init,
                             kind, use_weight, substrate, vertex_mask=True)

    def sharded_batched_push(self, src_val, active, out_init, kind,
                             use_weight, substrate):
        """Batched multi-source push (core/multisource.py): ``src_val`` /
        ``active`` / ``out_init`` are (B, n_pad) lane matrices.  Each shard
        runs its local relax vmapped over the lane axis — the edge shard is
        fetched once for all B lanes — and the whole (B, n_pad) accumulator
        is reduced across the mesh in one collective.

        The structured reducers (cvc2d / owner1d) key on per-vertex
        ownership of a *single* replicated label vector; like the reversed
        push they degrade to the full-mesh reduce for batched lanes
        (``batched_comm_per_relax`` charges that rate).  min/max/or stay
        order-independent, so every lane is bitwise equal to the
        single-lane sharded relax — and hence to the unsharded reference
        (tests/test_multisource.py pins the ndev ∈ {1, 2, 4} matrix)."""
        neutral = gk.neutral_for(kind, out_init.dtype)
        axes = self.axes

        def local(vals, msk, out0, s, d, w):
            s, d, w = s[0], d[0], w[0]

            def lane(v1, m1, o1):
                return _local_relax(s, d, w, m1, v1,
                                    jnp.full_like(o1, neutral), kind,
                                    use_weight, True, substrate)

            acc = jax.vmap(lane)(vals, msk, out0)
            return _merge(out0, _cross_reduce(acc, axes, kind), kind)

        fn = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(P(), P(), P(), P(axes), P(axes), P(axes)),
            out_specs=P(), check_vma=False,
        )
        return fn(src_val, active, out_init, self.src, self.dst, self.w)

    def sharded_batched_det_push(self, src_val, active, out_init, use_weight):
        """Deterministic batched ``add``: the canonical-order fixed tree
        (``_det_add_flat``) vmapped over the lane axis — each lane's sum
        associates in exactly the single-lane deterministic order, so
        batched float results stay bitwise identical to per-lane runs
        across every placement × ndev cell."""
        return jax.vmap(
            lambda v, a, o: _det_add_flat(self.src_idx, self.col_idx,
                                          self.edge_w, v, o, use_weight,
                                          active=a)
        )(src_val, active, out_init)

    def batched_comm_per_relax(self, lanes: int, itemsize: int = 4):
        """Analytic (elems, bytes, hops) of ONE batched label reduction:
        the (lanes, n_pad) accumulator crosses the mesh at the full-mesh
        rate (the structured reducers degrade for batched lanes)."""
        d = self.ndev
        if d <= 1:
            return 0, 0, 0
        elems = d * (d - 1) * self.n_pad * lanes
        return elems, elems * itemsize, len(self.axes)

    def sharded_pull_dense(self, src_val, active, out_init, kind, use_weight,
                           substrate):
        assert self.has_csc, "pull on a ShardedGraph needs shard_graph(g) " \
                             "with build_csc=True on the source Graph"
        return _edge_scatter(self.mesh, self.axes, self._reducer(),
                             self.in_nbr, self.in_dst, self.in_w, src_val,
                             active, out_init, kind, use_weight, substrate,
                             vertex_mask=True)

    def sharded_det_push(self, src_val, active, out_init, use_weight,
                         reverse=False):
        """Deterministic ``add`` push: canonical-order fixed tree over the
        flat out-edge views (see ``_det_add_flat``).  ``reverse`` swaps the
        endpoint roles; the canonical re-sort keys on the *new* roles, so
        the association order still matches the single-device reversed
        deterministic path exactly."""
        s, d = ((self.col_idx, self.src_idx) if reverse
                else (self.src_idx, self.col_idx))
        return _det_add_flat(s, d, self.edge_w,
                             src_val, out_init, use_weight, active=active)

    def sharded_relax_edges(self, src_val, edge_mask, out_init, kind,
                            use_weight, substrate):
        """Full edge list under a per-edge validity mask: the (m_pad,)
        mask is aligned with the flat shard views, so it reshards into
        (D, epd) alongside the edges."""
        mask2 = edge_mask.reshape(self.ndev, self.epd)
        return _edge_scatter(self.mesh, self.axes, self._reducer(), self.src,
                             self.dst, self.w, src_val, mask2, out_init,
                             kind, use_weight, substrate, vertex_mask=False)

    def sharded_det_relax_edges(self, src_val, edge_mask, out_init,
                                use_weight):
        return _det_add_flat(self.src_idx, self.col_idx, self.edge_w,
                             src_val, out_init, use_weight, valid=edge_mask)

    def sharded_intersect(self, adj, osrc, odst, substrate):
        """Edge-chunk-sharded oriented intersection for triangle counting:
        each device counts its (epd_t,) slice of the canonical oriented
        edge list through the substrate's intersect kernel, then a single
        ``psum`` of the exact int32 partials — the count is identical at
        every (placement, ndev).  ``osrc``/``odst`` are (D, epd_t),
        sentinel-padded."""
        sent, axes = self.sentinel, self.axes

        def local(a, s, d):
            s, d = s[0], d[0]
            if substrate == "pallas":
                c = gk.intersect_count(a, s, d, sentinel=sent)
            else:
                c = gk.intersect_ref(a, s, d, sent)
            return jax.lax.psum(jnp.asarray(c, jnp.int32), axes)

        fn = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(P(), P(axes), P(axes)),
            out_specs=P(), check_vma=False,
        )
        return fn(adj, osrc, odst)

    def sharded_det_pull(self, src_val, active, out_init, use_weight):
        assert self.has_csc
        return _det_add_flat(self.in_nbr.reshape(-1), self.in_dst.reshape(-1),
                             self.in_w.reshape(-1), src_val, out_init,
                             use_weight, active=active)

    def sharded_advance(self, f: SparseFrontier, budget: int, substrate):
        """Merge-path expansion of a replicated frontier, per shard: each
        device binary-searches its own shard-local degree sums, so the
        ``budget`` edge slots are per-device (the ladder rung is per-shard).
        """
        epd, sent = self.epd, self.sentinel

        def local(idx, count, deg, rp, ci, w):
            deg, rp, ci, w = deg[0], rp[0], ci[0], w[0]
            adv = gk.advance_frontier if substrate == "pallas" else gk.advance_ref
            s, d, ww, v, t = adv(idx, count, deg, rp, ci, w,
                                 budget=budget, sentinel=sent, m_pad=epd)
            t = jnp.asarray(t, jnp.int32).reshape(1)
            return s[None], d[None], ww[None], v[None], t

        fn = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(P(), P(), P(self.axes), P(self.axes), P(self.axes),
                      P(self.axes)),
            out_specs=(P(self.axes),) * 5, check_vma=False,
        )
        s, d, w, v, totals = fn(f.idx, f.count, self.shard_deg,
                                self.shard_row_ptr, self.dst, self.w)
        return ShardedEdgeBatch(mesh=self.mesh, axes=self.axes, src=s, dst=d,
                                w=w, valid=v, totals=totals,
                                red=self._reducer())

    def sharded_sparse_round(self, src_val, mask, out_init, kind, use_weight,
                             capacity, budget, substrate):
        """One fully shard-local data-driven round (the per-shard frontier
        ladder): compaction, merge-path advance, overflow detection, and
        escalation all run *inside* ``shard_map``.

        Each device compacts ``mask`` restricted to vertices with local
        edges into its own ``capacity``-slot worklist and expands it over
        its shard.  A shard whose worklist or edge mass overflows the rung
        (a hub-heavy shard) escalates **alone** to a shard-local dense
        relax of its masked edges — the same message set, so labels stay
        bitwise identical — instead of forcing a global dense round.  The
        per-shard escalation flags are summed with a tiny ``psum`` that is
        dataflow-independent of the relax, so it is dispatched before the
        heavy local relax and the cross-device label reduce; XLA is free to
        overlap the scalar collective (and the host's next rung pick) with
        them.  Returns ``(merged_labels, escalated_shard_count)``.

        ``lax.while_loop``-body safe by construction: the ``shard_map``
        (collectives included) nests under the fused engine's rung
        while_loop, and the escalation ``psum`` result is a replicated
        device int32 the loop accumulates in its carry — it is never
        fetched to the host per round, only once per rung stretch.
        """
        epd, sent, axes = self.epd, self.sentinel, self.axes
        red = self._reducer()
        neutral = gk.neutral_for(kind, out_init.dtype)

        def local(vals, msk, out0, deg, rp, s_all, d_all, w_all):
            deg, rp = deg[0], rp[0]
            s_all, d_all, w_all = s_all[0], d_all[0], w_all[0]
            idx, count_l = compact_local(msk, deg, capacity, sent)
            adv = (gk.advance_frontier if substrate == "pallas"
                   else gk.advance_ref)
            bs, bd, bw, bv, total = adv(idx, count_l, deg, rp, d_all, w_all,
                                        budget=budget, sentinel=sent,
                                        m_pad=epd)
            esc = (count_l > capacity) | (jnp.asarray(total, jnp.int32) >
                                          budget)
            # small, relax-independent collective: issued first so it can
            # overlap the local relax + label reduce below
            n_esc = jax.lax.psum(esc.astype(jnp.int32), axes)
            neutral_init = jnp.full_like(out0, neutral)

            def sparse_branch(_):
                return _local_relax(bs, bd, bw, bv, vals, neutral_init, kind,
                                    use_weight, False, substrate)

            def dense_branch(_):
                return _local_relax(s_all, d_all, w_all, msk, vals,
                                    neutral_init, kind, use_weight, True,
                                    substrate)

            acc = jax.lax.cond(esc, dense_branch, sparse_branch, None)
            return _merge(out0, red.reduce(acc, kind), kind), n_esc

        fn = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(P(), P(), P(), P(axes), P(axes), P(axes), P(axes),
                      P(axes)),
            out_specs=(P(), P()), check_vma=False,
        )
        return fn(src_val, mask, out_init, self.shard_deg,
                  self.shard_row_ptr, self.src, self.dst, self.w)


def _num_devices(mesh: Mesh, axes) -> int:
    return int(np.prod([mesh.shape[a] for a in axes]))


def _home(sg: ShardedGraph) -> ShardedGraph:
    """Device-put shard arrays one-per-device, vertex arrays replicated."""
    edge = NamedSharding(sg.mesh, P(sg.axes))
    rep = NamedSharding(sg.mesh, P())
    fields = dict(
        src=jax.device_put(sg.src, edge),
        dst=jax.device_put(sg.dst, edge),
        w=jax.device_put(sg.w, edge),
        shard_row_ptr=jax.device_put(sg.shard_row_ptr, edge),
        shard_deg=jax.device_put(sg.shard_deg, edge),
        out_deg=jax.device_put(sg.out_deg, rep),
    )
    if sg.has_csc:
        fields.update(
            in_nbr=jax.device_put(sg.in_nbr, edge),
            in_dst=jax.device_put(sg.in_dst, edge),
            in_w=jax.device_put(sg.in_w, edge),
        )
    if sg.red is not None and sg.red.own_idx is not None:
        fields["red"] = dataclasses.replace(
            sg.red,
            own_idx=jax.device_put(sg.red.own_idx, rep),
            own_valid=jax.device_put(sg.red.own_valid, rep),
        )
    return dataclasses.replace(sg, **fields)


def _build_reducer(pg: PartitionedGraph, mesh: Mesh, axes, reducer: str,
                   n_pad: int, block_size: int) -> CrossReducer:
    """Pick the communication-avoiding mode the partition supports.

    ``partition_2d`` on a 2-axis mesh gets the CVC column-reduce/row-gather
    structure; ``partition_1d`` (or a 2-D cut collapsed onto one axis) gets
    the owner-targeted reduce-scatter; everything else — including
    ``reducer="full"`` and single-device meshes — keeps the full-mesh
    reduce.
    """
    ndev = pg.ndev
    if reducer == "full" or ndev == 1:
        return CrossReducer(mode="full", axes=tuple(axes), rows=ndev, cols=1)
    if reducer != "cvc":
        raise ValueError(f"unknown reducer {reducer!r}; pick 'cvc' or 'full'")
    owner = np.asarray(pg.reduce_owner)
    if pg.scheme == "cvc" and len(axes) == 2 and pg.cols > 1:
        idx, valid = pl.owner_layout(owner, pg.cols)
        return CrossReducer(mode="cvc2d", axes=tuple(axes), rows=pg.rows,
                            cols=pg.cols, own_idx=jnp.asarray(idx),
                            own_valid=jnp.asarray(valid))
    if len(axes) == 1:
        own = owner if pg.scheme == "oec" else pl.vertex_owner(
            n_pad, block_size, ndev, pg.policy)
        idx, valid = pl.owner_layout(np.asarray(own), ndev)
        return CrossReducer(mode="owner1d", axes=tuple(axes), rows=ndev,
                            cols=1, own_idx=jnp.asarray(idx),
                            own_valid=jnp.asarray(valid))
    # multi-axis mesh without a matching 2-D cut: no structure to exploit
    return CrossReducer(mode="full", axes=tuple(axes), rows=ndev, cols=1)


def shard_graph(
    g: Graph,
    mesh: Mesh,
    axes: Tuple[str, ...] = ("data",),
    policy: str = "blocked",
    scheme: str = "oec",
    grid: Optional[Tuple[int, int]] = None,
    reducer: str = "cvc",
) -> ShardedGraph:
    """Partition ``g``'s edges over ``mesh`` and home them by ``policy``.

    ``scheme="oec"`` uses ``partition_1d`` (owner = source vertex);
    ``scheme="cvc"`` uses ``partition_2d`` over ``grid=(rows, cols)`` with
    ``rows * cols == ndev``.  ``reducer`` selects the cross-device label
    reduction: ``"cvc"`` (default) keys the communication-avoiding
    structure on the partition (column reduce + row gather for 2-D grids,
    owner-targeted reduce-scatter for 1-D cuts); ``"full"`` keeps the PR 2
    full-mesh all-reduce as the measurable baseline.  The result runs
    through ``SparseLadderEngine`` and ``run_dense`` unmodified.
    """
    ndev = _num_devices(mesh, axes)
    if scheme == "cvc":
        rows, cols = grid if grid is not None else (ndev, 1)
        assert rows * cols == ndev, (rows, cols, ndev)
        if len(axes) == 2:
            assert (mesh.shape[axes[0]], mesh.shape[axes[1]]) == (rows, cols), \
                "grid must match the mesh axes (rows, cols)"
        pg = partition_2d(g, rows, cols, policy=policy)
    else:
        pg = partition_1d(g, ndev, policy=policy)

    in_fields = {}
    if g.has_csc:
        if scheme == "cvc":
            pgi = partition_2d(g, rows, cols, policy=policy, direction="in")
        else:
            pgi = partition_1d(g, ndev, policy=policy, direction="in")
        in_fields = dict(in_nbr=pgi.src, in_dst=pgi.dst, in_w=pgi.w)

    red = _build_reducer(pg, mesh, axes, reducer, g.n_pad, g.block_size)
    sg = ShardedGraph(
        n=g.n, m=g.m, n_pad=g.n_pad, block_size=g.block_size,
        ndev=ndev, epd=pg.epd, scheme=scheme, placement=policy,
        axes=tuple(axes), mesh=mesh,
        src=pg.src, dst=pg.dst, w=pg.w,
        shard_row_ptr=pg.row_ptr, shard_deg=pg.deg, out_deg=pg.out_deg,
        red=red,
        **in_fields,
    )
    return _home(sg)
