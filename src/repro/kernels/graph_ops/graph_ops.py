"""Pallas edge-relaxation kernels — the graph engine's hot-path substrate.

Four kernels cover every operator the engine lowers (push, pull, sparse
advance + batch relax, oriented intersection), all blocked to the graph's
``block_size`` granularity (the paper's huge-page analogue, P2 — per-block
DMA, never per-element):

* ``_edge_relax_kernel`` — grid over **edge blocks**; each step loads one
  ``(1, block_e)`` tile of the COO/CSC edge arrays, gathers carried values,
  masks (by an active-vertex bitmap for push/pull, a per-slot validity mask
  for batch relax) and reduces into the vertex accumulator, which is
  **revisited** across the whole grid (sequential TPU grid → race-free
  read-modify-write, same structure as spmm_bsr's output accumulation).

* ``_advance_kernel`` — merge-path frontier expansion: grid over **budget
  blocks**.  The running degree sum of the compacted frontier is computed
  once into VMEM scratch (persists across grid steps); every budget slot
  then binary-searches it so a 3M-degree hub and a degree-1 leaf cost the
  same per-slot work.  The fixed edge-slot budget assignment happens
  *inside* the kernel — host code only picks the ladder rung.

* ``_intersect_kernel`` — triangle counting's sorted intersection: grid over
  **oriented-edge blocks**; each step gathers the sorted oriented-adjacency
  rows of both endpoints and counts merge hits by branchless binary search
  (``ref.sorted_lower_bound`` — the identical compare/select code as the
  jnp substrate, so the int32 counts are bitwise equal).  The scalar count
  is revisited across the sequential grid, same race-free accumulation as
  the edge-relax output.

Reductions: min / max / add / or (or = scatter-max over uint8; the wrapper
in ops.py widens bool accumulators).  All formulas mirror ref.py term for
term, so min/max/or results are bitwise identical to the jnp substrate and
add differs only by float summation order (exact on integer-valued data —
what the parity suite pins down).

On CPU the kernels run under ``interpret=True`` (the correctness path).
The TPU compiler refuses all four (the ``(1, block_e)`` tiles, then the
in-kernel 1-D gather and scatter-min); the package README lists the
refusals and ``tests/test_tpu_compile.py`` pins them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import edge_message, neutral_for, sorted_lower_bound


def _reduce_into(cur, dst, msg, kind: str):
    """In-kernel scatter reduction (or-kind arrives widened to uint8)."""
    ref = cur.at[dst]
    if kind == "min":
        return ref.min(msg)
    if kind in ("max", "or"):
        return ref.max(msg)
    if kind == "add":
        return ref.add(msg)
    raise ValueError(kind)


def _edge_relax_kernel(sv_ref, mask_ref, init_ref, s_ref, d_ref, w_ref,
                       o_ref, *, kind: str, use_weight: bool,
                       vertex_mask: bool):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        o_ref[...] = init_ref[...]

    s = s_ref[0]
    d = d_ref[0]
    w = w_ref[0]
    v = sv_ref[...][s]
    msg = edge_message(v, w, kind, use_weight)
    act = mask_ref[...][s] if vertex_mask else mask_ref[0]
    neutral = neutral_for(kind, o_ref.dtype)
    msg = jnp.where(act, msg.astype(o_ref.dtype), neutral)
    o_ref[...] = _reduce_into(o_ref[...], d, msg, kind)


def edge_relax_pallas(src, dst, w, mask, src_val, out_init, *, kind: str,
                      use_weight: bool, vertex_mask: bool, block_e: int,
                      interpret: bool):
    """Blocked scatter-relax over an edge list.

    ``mask`` is a vertex bitmap (n_pad,) when ``vertex_mask`` else a per-edge
    validity mask (m,).  ``m`` must be a multiple of ``block_e``.
    """
    m = src.shape[0]
    n_pad = out_init.shape[0]
    assert m % block_e == 0, (m, block_e)
    nb = m // block_e

    full = lambda shape: pl.BlockSpec(shape, lambda b: (0,) * len(shape))
    edge = pl.BlockSpec((1, block_e), lambda b: (b, 0))
    mask_spec = full(mask.shape) if vertex_mask else edge
    mask_in = mask if vertex_mask else mask.reshape(nb, block_e)

    return pl.pallas_call(
        functools.partial(_edge_relax_kernel, kind=kind,
                          use_weight=use_weight, vertex_mask=vertex_mask),
        grid=(nb,),
        in_specs=[full(src_val.shape), mask_spec, full((n_pad,)),
                  edge, edge, edge],
        out_specs=full((n_pad,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), out_init.dtype),
        interpret=interpret,
    )(src_val, mask_in, out_init,
      src.reshape(nb, block_e), dst.reshape(nb, block_e),
      w.reshape(nb, block_e))


def _intersect_kernel(adj_ref, s_ref, d_ref, out_ref, *, sentinel: int):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        out_ref[0] = jnp.int32(0)

    adj = adj_ref[...]
    s = s_ref[0]
    d = d_ref[0]
    nu = adj[s]                         # (block_e, dmax) candidates
    nv = adj[d]                         # (block_e, dmax) sorted targets
    pos = sorted_lower_bound(nv, nu)    # same code as ref.intersect_ref
    dmax = adj.shape[-1]
    hit = jnp.take_along_axis(nv, jnp.clip(pos, 0, dmax - 1), axis=-1) == nu
    hit &= nu != sentinel
    # scalar output revisited across the sequential grid: race-free += like
    # the edge-relax accumulator
    out_ref[0] = out_ref[0] + jnp.sum(hit.astype(jnp.int32))


def intersect_pallas(adj, src, dst, *, sentinel: int, block_e: int,
                     interpret: bool):
    """Blocked oriented-intersection count (tc's hot loop): grid over edge
    blocks of ``block_e`` oriented edges; each step gathers the two sorted
    adjacency rows per edge and counts sorted-merge hits by binary search.
    ``src.shape[0]`` must be a multiple of ``block_e``; returns int32."""
    e = src.shape[0]
    assert e % block_e == 0, (e, block_e)
    nb = e // block_e

    full = lambda shape: pl.BlockSpec(shape, lambda b: (0,) * len(shape))
    edge = pl.BlockSpec((1, block_e), lambda b: (b, 0))

    out = pl.pallas_call(
        functools.partial(_intersect_kernel, sentinel=sentinel),
        grid=(nb,),
        in_specs=[full(adj.shape), edge, edge],
        out_specs=full((1,)),
        out_shape=jax.ShapeDtypeStruct((1,), jnp.int32),
        interpret=interpret,
    )(adj, src.reshape(nb, block_e), dst.reshape(nb, block_e))
    return out[0]


def _advance_kernel(fidx_ref, fcount_ref, deg_ref, rowptr_ref, col_ref,
                    ew_ref, src_ref, dst_ref, w_ref, valid_ref, total_ref,
                    cum_ref, *, cap: int, block_b: int, m_pad: int,
                    sentinel: int):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _prefix():
        # running degree sum of the compacted frontier, once per call;
        # VMEM scratch persists across the (sequential) grid
        in_list = jnp.arange(cap, dtype=jnp.int32) < jnp.minimum(
            fcount_ref[0], cap)
        deg = jnp.where(in_list, deg_ref[...][fidx_ref[...]], 0)
        cum_ref[...] = jnp.cumsum(deg)

    cum = cum_ref[...]
    total = cum[cap - 1]

    @pl.when(b == 0)
    def _total():
        total_ref[0] = total

    # merge-path: slot j belongs to the frontier vertex whose cumulative
    # degree range covers j — equal work per slot regardless of skew
    j = b * block_b + jnp.arange(block_b, dtype=jnp.int32)
    k = jnp.searchsorted(cum, j, side="right").astype(jnp.int32)
    k = jnp.clip(k, 0, cap - 1)
    prev = jnp.where(k > 0, cum[jnp.maximum(k - 1, 0)], 0)
    u = fidx_ref[...][k]
    e = rowptr_ref[...][u] + (j - prev)
    valid = j < total
    e = jnp.where(valid, e, m_pad - 1)  # padded edge → sentinel dst, w=0
    u = jnp.where(valid, u, sentinel)
    src_ref[0] = u
    dst_ref[0] = col_ref[...][e]
    w_ref[0] = ew_ref[...][e]
    valid_ref[0] = valid


def advance_pallas(f_idx, f_count, out_deg, row_ptr, col_idx, edge_w, *,
                   budget: int, sentinel: int, m_pad: int, block_b: int,
                   interpret: bool):
    """Merge-path expansion of a compacted frontier into ``budget`` edge
    slots.  Returns ``(src, dst, w, valid, total)``; ``budget`` must be a
    multiple of ``block_b``."""
    cap = f_idx.shape[0]
    assert budget % block_b == 0, (budget, block_b)
    nb = budget // block_b

    full = lambda shape: pl.BlockSpec(shape, lambda b: (0,) * len(shape))
    slot = lambda dt: jax.ShapeDtypeStruct((nb, block_b), dt)

    src, dst, w, valid, total = pl.pallas_call(
        functools.partial(_advance_kernel, cap=cap, block_b=block_b,
                          m_pad=m_pad, sentinel=sentinel),
        grid=(nb,),
        in_specs=[full((cap,)), full((1,)), full(out_deg.shape),
                  full(row_ptr.shape), full(col_idx.shape),
                  full(edge_w.shape)],
        out_specs=[pl.BlockSpec((1, block_b), lambda b: (b, 0))] * 4
        + [full((1,))],
        out_shape=[slot(jnp.int32), slot(jnp.int32), slot(edge_w.dtype),
                   slot(jnp.bool_), jax.ShapeDtypeStruct((1,), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((cap,), jnp.int32)],
        interpret=interpret,
    )(f_idx, f_count.reshape(1).astype(jnp.int32), out_deg, row_ptr,
      col_idx, edge_w)
    return (src.reshape(budget), dst.reshape(budget), w.reshape(budget),
            valid.reshape(budget), total[0])
