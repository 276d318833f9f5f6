"""Block-padded CSR/CSC/COO graph container.

This is the paper's core data structure, adapted to TPU constraints:

* All arrays are **statically shaped** and padded to a multiple of
  ``block_size`` edges / vertices.  ``block_size`` is the analogue of the
  paper's *huge pages* (P2): placement, sharding and kernel tiling all operate
  on whole blocks, never on individual elements, so per-element metadata (the
  TLB-entry analogue) never exists.
* Vertex arrays carry **one sentinel slot** at index ``n_pad - 1``.  Padded
  edges point at the sentinel, so scatters from padding are harmless and no
  masks are needed on the hot path.
* Both CSR (out-edges, push direction) and CSC (in-edges, pull direction) can
  be materialised.  Direction-optimizing algorithms need both — the paper
  notes this doubles the memory footprint, and we keep it optional for the
  same reason.

The container is a pytree, so it can be donated, sharded with
``jax.device_put`` + NamedSharding (see ``placement.py``) and passed through
``jax.jit`` / ``shard_map`` unchanged.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.graph_ops.ref import scatter_at


def _pad_to(x: np.ndarray, size: int, fill) -> np.ndarray:
    if x.shape[0] == size:
        return x
    out = np.full((size,) + x.shape[1:], fill, dtype=x.dtype)
    out[: x.shape[0]] = x
    return out


def round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Graph:
    """Static-shape padded graph.

    Attributes
    ----------
    n, m:          true vertex / edge counts (static metadata).
    n_pad, m_pad:  padded counts; ``n_pad - 1`` is the sentinel vertex.
    row_ptr:       (n_pad + 1,) CSR offsets over *out*-edges (sentinel rows empty).
    col_idx:       (m_pad,) destination of each out-edge; padding = sentinel.
    src_idx:       (m_pad,) source of each out-edge (COO expansion of row_ptr).
    edge_w:        (m_pad,) float32 weights (1.0 when unweighted, 0 on padding).
    in_row_ptr / in_col_idx / in_src_idx / in_edge_w:
                   optional CSC mirror (in-edges), same conventions.
    out_deg:       (n_pad,) true out-degree per vertex (0 on sentinel).
    """

    # static metadata
    n: int = dataclasses.field(metadata=dict(static=True))
    m: int = dataclasses.field(metadata=dict(static=True))
    n_pad: int = dataclasses.field(metadata=dict(static=True))
    m_pad: int = dataclasses.field(metadata=dict(static=True))
    block_size: int = dataclasses.field(metadata=dict(static=True))

    # CSR (push direction)
    row_ptr: jax.Array
    col_idx: jax.Array
    src_idx: jax.Array
    edge_w: jax.Array
    out_deg: jax.Array

    # CSC (pull direction) — optional
    in_row_ptr: Optional[jax.Array] = None
    in_col_idx: Optional[jax.Array] = None
    in_src_idx: Optional[jax.Array] = None
    in_edge_w: Optional[jax.Array] = None
    in_deg: Optional[jax.Array] = None

    @property
    def sentinel(self) -> int:
        return self.n_pad - 1

    @property
    def has_csc(self) -> bool:
        return self.in_row_ptr is not None

    def vertex_full(self, fill, dtype) -> jax.Array:
        """A vertex-indexed array (with sentinel slot) filled with ``fill``."""
        return jnp.full((self.n_pad,), fill, dtype=dtype)

    def valid_vertex_mask(self) -> jax.Array:
        return jnp.arange(self.n_pad) < self.n

    def budget_edge_mass(self, mask: jax.Array) -> jax.Array:
        """Frontier edge mass a sparse-advance budget must cover.  On a
        single partition that is the whole frontier's out-degree sum; the
        sharded container overrides this with the max per-shard mass."""
        return jnp.sum(jnp.where(mask, self.out_deg, 0))

    @property
    def csr_bytes(self) -> int:
        """Bytes of the padded CSR edge arrays (col_idx + src_idx + edge_w)
        — the quantity the tiered-memory path budgets against.  Vertex
        arrays (O(n)) always stay device-resident; the edge arrays (O(m))
        are what outgrows the fast tier on the paper's massive inputs."""
        return self.m_pad * (4 + 4 + 4)


def shard_ranges(g: Graph, nshards: int):
    """Block-granular contiguous shard cut of the CSR edge arrays.

    Returns ``(vtx_bounds, edge_bounds)``: shard s owns the out-edges of
    vertices ``[vtx_bounds[s], vtx_bounds[s+1])``, which occupy the CSR
    slice ``[edge_bounds[s], edge_bounds[s+1])`` — contiguous because
    ``from_coo`` lays edges out (src, dst)-sorted.  The vertex cut is the
    ``placement.shard_owner("blocked")`` rule (ceil(n_pad / nshards),
    rounded up to whole ``block_size`` blocks — placement never operates
    below block granularity, the huge-page rule P2), so tiered host shards
    reuse exactly the ``partition_1d`` homing metadata.
    """
    per = -(-g.n_pad // nshards)            # ceil: the blocked-OEC cut
    per = round_up(per, g.block_size)
    vtx = np.minimum(np.arange(nshards + 1, dtype=np.int64) * per, g.n_pad)
    rp = np.asarray(g.row_ptr)
    edge = rp[vtx].astype(np.int64)
    return vtx, edge


def from_coo(
    src: np.ndarray,
    dst: np.ndarray,
    n: int,
    weights: Optional[np.ndarray] = None,
    *,
    block_size: int = 512,
    build_csc: bool = False,
    symmetrize: bool = False,
    dedup: bool = True,
) -> Graph:
    """Build a padded Graph from host COO arrays (numpy, not traced)."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    if weights is None:
        w = np.ones(src.shape[0], dtype=np.float32)
    else:
        w = np.asarray(weights, dtype=np.float32)

    if symmetrize:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
        w = np.concatenate([w, w])

    if dedup:
        # self-loops are dropped (no algorithm here relaxes them, and the
        # oriented tc adjacency requires their absence); duplicate
        # (src, dst) edges keep the MINIMUM weight — keeping an arbitrary
        # duplicate (the old first-in-sorted-key-order rule) made weighted
        # sssp/bfs results depend on input edge order, since which weight
        # survived was an accident of the permutation
        keep = src != dst
        src, dst, w = src[keep], dst[keep], w[keep]
        key = src * np.int64(n) + dst
        # one int64 argsort (a two-key lexsort is ~10x slower at 1e7+
        # edges), then the smallest weight of each key's run
        order = np.argsort(key, kind="stable")
        key = key[order]
        first = np.flatnonzero(np.r_[True, key[1:] != key[:-1]]
                               if key.size else key)
        w = np.fmin.reduceat(w[order], first) if first.size else w
        src, dst = src[order[first]], dst[order[first]]

    m = int(src.shape[0])
    # sentinel gets its own slot; vertex arrays padded to block multiple
    n_pad = round_up(n + 1, block_size)
    m_pad = round_up(max(m, 1), block_size)
    sentinel = n_pad - 1

    def build(direction_src, direction_dst):
        order = np.argsort(direction_src * np.int64(n_pad) + direction_dst,
                           kind="stable")
        s, d, ww = direction_src[order], direction_dst[order], w[order]
        counts = np.bincount(s, minlength=n_pad).astype(np.int32)
        counts[sentinel] = 0
        rp = np.zeros(n_pad + 1, dtype=np.int32)
        np.cumsum(counts, out=rp[1:])
        ci = _pad_to(d.astype(np.int32), m_pad, sentinel)
        si = _pad_to(s.astype(np.int32), m_pad, sentinel)
        ew = _pad_to(ww, m_pad, 0.0)
        deg = counts
        return rp, ci, si, ew, deg

    csr = tuple(jnp.asarray(a) for a in build(src, dst))
    kwargs = {}
    if build_csc:
        # a symmetrized, deduplicated edge set (with its symmetric minimum
        # weights) sorts the same by (dst, src) as by (src, dst): its CSC
        # is its CSR, and shares the device arrays
        csc = csr if symmetrize and dedup else tuple(
            jnp.asarray(a) for a in build(dst, src))
        # for CSC: "row" is the destination, the stored index is the source
        kwargs = dict(
            in_row_ptr=csc[0],
            in_col_idx=csc[1],   # in-neighbour (original src)
            in_src_idx=csc[2],   # the destination vertex per in-edge
            in_edge_w=csc[3],
            in_deg=csc[4],
        )

    rp, ci, si, ew, deg = csr
    return Graph(
        n=n,
        m=m,
        n_pad=n_pad,
        m_pad=m_pad,
        block_size=block_size,
        row_ptr=rp,
        col_idx=ci,
        src_idx=si,
        edge_w=ew,
        out_deg=deg,
        **kwargs,
    )


def to_dense(g: Graph) -> np.ndarray:
    """Dense adjacency (host, test-sized graphs only)."""
    a = np.zeros((g.n, g.n), dtype=np.float32)
    src = np.asarray(g.src_idx)
    dst = np.asarray(g.col_idx)
    w = np.asarray(g.edge_w)
    valid = (src < g.n) & (dst < g.n)
    a[src[valid], dst[valid]] = w[valid]
    return a


@partial(jax.jit, static_argnames=("n_pad",))
def degrees_from_edges(src: jax.Array, n_pad: int) -> jax.Array:
    return scatter_at(jnp.zeros((n_pad,), jnp.int32), src,
                      jnp.ones(src.shape, jnp.int32), "add")
