"""Pure-jnp reference semantics for the graph edge-relaxation operators.

This module is the **array-level contract** both substrates implement:

* it takes raw arrays (edge lists, CSC triples, frontier buffers), never the
  ``Graph``/``SparseFrontier`` containers — the kernel layer must not know
  about the engine's data structures (same layering as flash_attention
  taking q/k/v);
* it is the oracle the Pallas kernels are parity-tested against, and the
  body of the ``"jnp"`` substrate in ``core/operators.py``.

Reduction kinds: ``min`` / ``max`` (tropical relax, message = v + w),
``add`` (weighted contribution, message = v * w) and ``or`` (boolean
reachability; reduced as max over uint8 so duplicate destinations combine
correctly under scatter).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

KINDS = ("min", "max", "add", "or")


def neutral_for(kind: str, dtype) -> jax.Array:
    """Identity element of the reduction, in the accumulator's dtype."""
    dtype = jnp.dtype(dtype)
    if kind == "add":
        return jnp.zeros((), dtype)
    if kind == "or":
        # False / 0: 'or' reduces as max over bool-as-uint8
        return jnp.zeros((), dtype)
    if dtype == bool:
        return jnp.array(kind == "min", dtype)
    big = jnp.finfo(dtype).max if jnp.issubdtype(dtype, jnp.inexact) else jnp.iinfo(dtype).max
    low = jnp.finfo(dtype).min if jnp.issubdtype(dtype, jnp.inexact) else jnp.iinfo(dtype).min
    if kind == "min":
        return jnp.array(big, dtype)
    if kind == "max":
        return jnp.array(low, dtype)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Scatters and compaction into vertex-sized arrays
# ---------------------------------------------------------------------------
# The TPU compiler keeps a scatter's operand in on-chip memory only up to a
# few million elements (3,145,728 compiles as such; 4,193,280 does not).
# Above that it sorts the updates first, and a program holding that sort
# takes 7-13 s to compile (graph500-22: n_pad = 4,194,816), against under
# a second for the same scatter split over operand ranges.  It sorts the
# updates of every 8- or 16-bit scatter, whatever its size, so those
# scatter in int32.  Its cumsum over a vertex array or a large frontier
# is likewise 4-20 s to compile, against under one when blocked.
# ``tests/test_tpu_compile.py`` pins all three.
SCATTER_CHUNK = 3 << 20   # most operand elements one scatter may hold
_SCAN_BLOCK = 1024


def scatter_at(out, idx, upd, op: str):
    """``getattr(out.at[..., idx], op)(upd)`` along the last axis of a 1-D
    or 2-D ``out``, in as few operand ranges of at most ``SCATTER_CHUNK``
    elements as it takes.  Each range sees every update, with the indices
    outside it dropped, so every element receives the same updates in the
    same order as one whole scatter."""
    if out.dtype.itemsize < 4 and not jnp.issubdtype(out.dtype, jnp.floating):
        wide = scatter_at(out.astype(jnp.int32), idx, upd.astype(jnp.int32),
                          op)
        return wide.astype(out.dtype)
    k = -(-out.size // SCATTER_CHUNK)
    if k <= 1:
        return getattr(out.at[..., idx], op)(upd)
    if out.ndim == 2:
        # one row (lane) at a time: each update is then visited once per
        # range of its own row, not once per range of the whole matrix
        return jnp.stack([scatter_at(out[r], idx, upd[r], op)
                          for r in range(out.shape[0])])
    n = out.shape[-1]
    size = -(-n // k)
    parts = []
    for lo in range(0, n, size):
        hi = min(lo + size, n)
        local = jnp.where((idx >= lo) & (idx < hi), idx - lo, hi - lo)
        parts.append(getattr(out[..., lo:hi].at[..., local], op)(
            upd, mode="drop"))
    return jnp.concatenate(parts, axis=-1)


def prefix_sum(x):
    """Inclusive cumulative sum of a 1-D array, scanned in blocks."""
    n = x.shape[0]
    rows = jnp.pad(x, (0, -n % _SCAN_BLOCK)).reshape(-1, _SCAN_BLOCK)
    inner = jnp.cumsum(rows, axis=1)
    tot = inner[:, -1]
    return (inner + (jnp.cumsum(tot) - tot)[:, None]).reshape(-1)[:n]


def compact_indices(mask, capacity: int, fill: int):
    """``jnp.nonzero(mask, size=capacity, fill_value=fill)[0]`` as int32:
    the first ``capacity`` set positions in ascending order, then
    ``fill``."""
    slot = jnp.where(mask, prefix_sum(mask.astype(jnp.int32)) - 1, capacity)
    out = jnp.full((capacity,), fill, jnp.int32)
    return scatter_at(out, slot, jnp.arange(mask.shape[0], dtype=jnp.int32),
                      "set")


def scatter_reduce(dst, msg, out, kind: str):
    """Reduce ``msg`` into ``out`` at positions ``dst``."""
    if kind in ("min", "max", "add"):
        return scatter_at(out, dst, msg, kind)
    if kind == "or":
        # scatter-max: duplicate destinations OR together (scatter-set
        # would pick an arbitrary duplicate)
        return scatter_at(out, dst, msg.astype(out.dtype), "max")
    raise ValueError(kind)


def edge_message(v, w, kind: str, use_weight: bool):
    """Per-edge message: tropical (v + w) for min/max, scaled (v * w) for
    add/or; the carried value alone when unweighted."""
    if not use_weight:
        return v
    return v + w if kind in ("min", "max") else v * w


def det_scatter_add(dst, msg, out):
    """Fixed-order scatter-add: stable-sort by destination, sum each
    destination's messages with a fixed-shape segmented (Hillis–Steele)
    tree, then add exactly one combined value per destination into ``out``.

    The association order is a function of the edge layout alone, never of
    the backend's scatter implementation, so float results are bitwise
    reproducible across substrates (both route here under
    ``operators.set_deterministic_add(True)``).  Costs one stable sort per
    relax — a no-op permutation on already-dst-sorted (CSC) edge lists.
    """
    m = int(msg.shape[0])
    order = jnp.argsort(dst, stable=True)
    seg = dst[order]
    val = msg[order]
    zero = jnp.zeros((), val.dtype)
    k = 1
    while k < m:
        shifted = jnp.concatenate([jnp.full((k,), zero), val[:-k]])
        same = jnp.concatenate(
            [jnp.zeros((k,), bool), seg[k:] == seg[:-k]])
        val = val + jnp.where(same, shifted, zero)
        k *= 2
    # last slot of each run holds the segment sum; everything else adds the
    # exact zero of the dtype, which cannot perturb the result
    is_tail = jnp.concatenate([seg[1:] != seg[:-1], jnp.ones((1,), bool)])
    return scatter_at(out, seg, jnp.where(is_tail, val, zero), "add")


def det_push_ref(src, dst, w, src_val, active, out_init,
                 use_weight: bool = True):
    """``push_ref(kind="add")`` with the deterministic fixed-order sum."""
    v = src_val[src]
    msg = edge_message(v, w, "add", use_weight)
    msg = jnp.where(active[src], msg.astype(out_init.dtype),
                    jnp.zeros((), out_init.dtype))
    return det_scatter_add(dst, msg, out_init)


def det_relax_ref(src, dst, w, valid, src_val, out_init,
                  use_weight: bool = True):
    """``relax_ref(kind="add")`` with the deterministic fixed-order sum."""
    v = src_val[src]
    msg = edge_message(v, w, "add", use_weight)
    msg = jnp.where(valid, msg.astype(out_init.dtype),
                    jnp.zeros((), out_init.dtype))
    return det_scatter_add(dst, msg, out_init)


def push_ref(src, dst, w, src_val, active, out_init, kind: str = "min",
             use_weight: bool = True):
    """Masked push over an edge list: relax every edge whose source is active."""
    v = src_val[src]
    msg = edge_message(v, w, kind, use_weight)
    neutral = neutral_for(kind, out_init.dtype)
    msg = jnp.where(active[src], msg.astype(out_init.dtype), neutral)
    return scatter_reduce(dst, msg, out_init, kind)


_COMBINE = {"min": jnp.minimum, "max": jnp.maximum, "add": jnp.add,
            "or": jnp.maximum}


def pull_ref(nbr, dst, w, src_val, active, out_init, kind: str = "min",
             use_weight: bool = True, row_ptr=None):
    """Pull over in-edges grouped by destination (``dst`` sorted ascending):
    ``sorted_segment_reduce`` merged into ``out_init``.  With the edges'
    ``row_ptr`` each vertex's result is gathered, else scattered."""
    neutral = neutral_for(kind, out_init.dtype)
    if (kind != "or" and src_val.dtype == out_init.dtype
            and (jnp.issubdtype(out_init.dtype, jnp.floating)
                 or not use_weight)):
        # one gather of pre-masked values: a message from an inactive
        # source is the neutral element (float weights below 2**100)
        v = jnp.where(active, src_val, neutral)[nbr]
        msg = edge_message(v, w, kind, use_weight).astype(out_init.dtype)
    else:
        msg = edge_message(src_val[nbr], w, kind, use_weight)
        msg = jnp.where(active[nbr], msg.astype(out_init.dtype), neutral)
    n = out_init.shape[0]
    if out_init.dtype == bool:
        # over int32 0/1 (a bool max is an or: neutral 0, not int32's min)
        red = sorted_segment_reduce(dst, msg.astype(jnp.int32),
                                    "or" if kind == "max" else kind, n,
                                    row_ptr).astype(bool)
    else:
        red = sorted_segment_reduce(dst, msg, kind, n, row_ptr)
    return _COMBINE[kind](out_init, red)


def sorted_segment_reduce(seg, val, kind: str, num_segments: int,
                          row_ptr=None):
    """Reduce ``val`` over the runs of equal ``seg`` (sorted ascending) into
    ``num_segments`` outputs, the neutral element where a segment is empty.
    A Hillis–Steele segmented scan (log2(len) whole-array passes), then
    each segment's last slot: gathered through ``row_ptr`` (len
    ``num_segments + 1``) when given, else scattered with sorted indices
    and one non-neutral update per segment.  Vector work where an unsorted
    scatter costs the TPU several nanoseconds per element, and ``add``
    associates as a fixed tree."""
    neutral = neutral_for(kind, val.dtype)
    m = val.shape[0]
    if m == 0:
        return jnp.full((num_segments,), neutral, val.dtype)
    comb = _COMBINE[kind]
    k = 1
    while k < m:
        shifted = jnp.concatenate([jnp.full((k,), neutral, val.dtype),
                                   val[:-k]])
        same = jnp.concatenate([jnp.zeros((k,), bool), seg[k:] == seg[:-k]])
        val = jnp.where(same, comb(val, shifted), val)
        k *= 2
    if row_ptr is not None:
        end = row_ptr[1:]
        tail = val[jnp.clip(end - 1, 0, m - 1)]
        return jnp.where(end > row_ptr[:-1], tail, neutral)
    is_tail = jnp.concatenate([seg[1:] != seg[:-1], jnp.ones((1,), bool)])
    out = jnp.full((num_segments,), neutral, val.dtype)
    op = "max" if kind == "or" else kind
    return getattr(out.at[seg], op)(jnp.where(is_tail, val, neutral),
                                    indices_are_sorted=True)


def sorted_lower_bound(rows, vals):
    """Branchless per-row lower bound: for each query ``vals[..., j]`` the
    index of the first element of ``rows[..., :]`` that is >= it (``rows``
    sorted ascending along the last axis).  Pure compare/select — the same
    code runs inside the Pallas intersect kernel, so both substrates
    produce identical (integer) positions."""
    dmax = rows.shape[-1]
    lo = jnp.zeros(vals.shape, jnp.int32)
    hi = jnp.full(vals.shape, dmax, jnp.int32)
    for _ in range(max(int(dmax).bit_length(), 1)):
        mid = (lo + hi) >> 1
        probe = jnp.take_along_axis(rows, jnp.clip(mid, 0, dmax - 1), axis=-1)
        less = probe < vals
        lo = jnp.where(less, mid + 1, lo)
        hi = jnp.where(less, hi, mid)
    return lo


def intersect_ref(adj, src, dst, sentinel: int):
    """Oriented triangle intersection count over an edge batch.

    ``adj``: (n_pad, dmax) sorted oriented adjacency, sentinel-padded rows.
    ``src``/``dst``: (e,) endpoints of oriented edges (sentinel on padding
    slots — ``adj[sentinel]`` is all-sentinel, so padded edges contribute
    0).  Returns the int32 total of |N+(src_i) ∩ N+(dst_i)| — exact, so
    results are bitwise identical across substrates, edge-chunk sizes and
    shard partitions.
    """
    nu = adj[src]                       # (e, dmax) candidates w in N+(u)
    nv = adj[dst]                       # (e, dmax) sorted search targets
    pos = sorted_lower_bound(nv, nu)
    dmax = adj.shape[-1]
    hit = jnp.take_along_axis(nv, jnp.clip(pos, 0, dmax - 1), axis=-1) == nu
    hit &= nu != sentinel
    return jnp.sum(hit.astype(jnp.int32))


def advance_ref(f_idx, f_count, out_deg, row_ptr, col_idx, edge_w,
                budget: int, sentinel: int, m_pad: int):
    """Merge-path expansion of a compacted frontier into ``budget`` edge
    slots.  Returns ``(src, dst, w, valid, total)`` — ``total`` is the true
    frontier edge mass (overflow check)."""
    cap = f_idx.shape[0]
    in_list = jnp.arange(cap) < jnp.minimum(f_count, cap)
    deg = jnp.where(in_list, out_deg[f_idx], 0)
    cum = prefix_sum(deg)
    total = cum[-1] if cap > 0 else jnp.int32(0)
    j = jnp.arange(budget, dtype=jnp.int32)
    k = jnp.searchsorted(cum, j, side="right").astype(jnp.int32)
    k = jnp.clip(k, 0, cap - 1)
    prev = jnp.where(k > 0, cum[jnp.maximum(k - 1, 0)], 0)
    u = f_idx[k]
    e = row_ptr[u] + (j - prev)
    valid = j < total
    e = jnp.where(valid, e, m_pad - 1)  # padded edge → sentinel dst, w=0
    u = jnp.where(valid, u, sentinel)
    return u, col_idx[e], edge_w[e], valid, total


def relax_ref(src, dst, w, valid, src_val, out_init, kind: str = "min",
              use_weight: bool = True):
    """Scatter-relax an expanded edge batch (per-edge validity mask)."""
    v = src_val[src]
    msg = edge_message(v, w, kind, use_weight)
    neutral = neutral_for(kind, out_init.dtype)
    msg = jnp.where(valid, msg.astype(out_init.dtype), neutral)
    return scatter_reduce(dst, msg, out_init, kind)


# ---------------------------------------------------------------------------
# Multi-source (batched-lane) relaxations — core/multisource.py
# ---------------------------------------------------------------------------
# One shared edge-structure fetch amortized over B label lanes: the edge
# arrays are gathered once, the per-lane values arrive as a (B, n_pad)
# matrix, and the scatter runs on axis 1 with a shared destination vector
# (``scatter_reduce`` on the (B, n_pad) accumulator).
# Per lane these compute exactly what push_ref / relax_ref compute, and the
# min/max/or reductions are order-independent, so each row is bitwise equal
# to the corresponding single-lane call (pinned by tests/test_multisource).


def batched_push_ref(src, dst, w, src_val, active, out_init,
                     kind: str = "min", use_weight: bool = True):
    """Masked push over an edge list for B lanes at once.

    ``src_val`` / ``active`` / ``out_init`` are (B, n_pad); the edge arrays
    are shared across lanes (fetched once — the MS-BFS amortization)."""
    v = src_val[:, src]                                   # (B, e)
    msg = edge_message(v, w[None, :], kind, use_weight)
    neutral = neutral_for(kind, out_init.dtype)
    msg = jnp.where(active[:, src], msg.astype(out_init.dtype), neutral)
    return scatter_reduce(dst, msg, out_init, kind)


def batched_relax_ref(src, dst, w, valid, src_val, active, out_init,
                      kind: str = "min", use_weight: bool = True):
    """Scatter-relax an expanded edge batch for B lanes: a slot fires in
    lane b when the slot is valid AND its source is in lane b's frontier
    (``active``).  The batch is expanded from the lanes' *union* frontier,
    so the per-lane mask restores exactly lane b's message multiset."""
    v = src_val[:, src]
    msg = edge_message(v, w[None, :], kind, use_weight)
    neutral = neutral_for(kind, out_init.dtype)
    msg = jnp.where(valid[None, :] & active[:, src],
                    msg.astype(out_init.dtype), neutral)
    return scatter_reduce(dst, msg, out_init, kind)
