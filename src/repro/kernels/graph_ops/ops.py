"""Public wrappers for the graph_ops Pallas kernels: jit caching, the
interpret-mode choice, block-size auto-pick, and bool→uint8 widening for
the ``or`` reduction.  ``core/operators.py`` routes here when the
``"pallas"`` substrate is selected; callers that want the raw kernels can
use these directly with arrays.

Interpret mode is taken only on the CPU backend.  Everywhere else the
kernels go to the TPU compiler, and a kernel it refuses fails with the
compiler's own error: there is no quiet fallback to the interpreter or to
the ``"jnp"`` substrate.  (Today the TPU compiler refuses all four
kernels; ``tests/test_tpu_compile.py`` pins the refusals.)"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .graph_ops import advance_pallas, edge_relax_pallas, intersect_pallas

# block tile target for edge/budget arrays; actual block is the largest
# divisor ≤ target so padded sizes from any graph block_size tile exactly
_BLOCK_TARGET = 1024


def _interpret() -> bool:
    """Interpret the kernels on the CPU backend only."""
    return jax.default_backend() == "cpu"


def _pick_block(size: int, target: int = _BLOCK_TARGET) -> int:
    return max(math.gcd(size, target), 1)


@functools.partial(
    jax.jit,
    static_argnames=("kind", "use_weight", "vertex_mask", "block_e",
                     "interpret"),
)
def _edge_relax_jit(src, dst, w, mask, src_val, out_init, kind, use_weight,
                    vertex_mask, block_e, interpret):
    widen = kind == "or" and out_init.dtype == bool
    if widen:
        src_val = src_val.astype(jnp.uint8)
        out_init = out_init.astype(jnp.uint8)
    out = edge_relax_pallas(
        src, dst, w, mask, src_val, out_init, kind=kind,
        use_weight=use_weight, vertex_mask=vertex_mask, block_e=block_e,
        interpret=interpret,
    )
    return out.astype(bool) if widen else out


def edge_relax(src, dst, w, mask, src_val, out_init, *, kind: str = "min",
               use_weight: bool = True, vertex_mask: bool = True,
               block_e: int | None = None, interpret: bool | None = None):
    """Blocked push/pull/batch relax over an edge list (see graph_ops.py).

    ``mask``: (n_pad,) active-vertex bitmap when ``vertex_mask`` (push/pull),
    else a per-edge validity mask aligned with ``src`` (batch relax).
    """
    if interpret is None:
        interpret = _interpret()
    if block_e is None:
        block_e = _pick_block(src.shape[0])
    return _edge_relax_jit(src, dst, w, mask, src_val, out_init, kind,
                           use_weight, vertex_mask, block_e, interpret)


@functools.partial(
    jax.jit,
    static_argnames=("budget", "sentinel", "m_pad", "block_b", "interpret"),
)
def _advance_jit(f_idx, f_count, out_deg, row_ptr, col_idx, edge_w, budget,
                 sentinel, m_pad, block_b, interpret):
    return advance_pallas(
        f_idx, f_count, out_deg, row_ptr, col_idx, edge_w, budget=budget,
        sentinel=sentinel, m_pad=m_pad, block_b=block_b, interpret=interpret,
    )


def advance_frontier(f_idx, f_count, out_deg, row_ptr, col_idx, edge_w, *,
                     budget: int, sentinel: int, m_pad: int,
                     block_b: int | None = None,
                     interpret: bool | None = None):
    """Merge-path frontier expansion into ``budget`` edge slots; returns
    ``(src, dst, w, valid, total)``."""
    if interpret is None:
        interpret = _interpret()
    if block_b is None:
        block_b = _pick_block(budget)
    return _advance_jit(f_idx, f_count, out_deg, row_ptr, col_idx, edge_w,
                        budget, sentinel, m_pad, block_b, interpret)


@functools.partial(
    jax.jit, static_argnames=("sentinel", "block_e", "interpret"),
)
def _intersect_jit(adj, src, dst, sentinel, block_e, interpret):
    return intersect_pallas(adj, src, dst, sentinel=sentinel,
                            block_e=block_e, interpret=interpret)


def intersect_count(adj, src, dst, *, sentinel: int,
                    block_e: int | None = None,
                    interpret: bool | None = None):
    """Blocked oriented-intersection count for a batch of oriented edges
    (see graph_ops.py); returns an exact int32 scalar."""
    if interpret is None:
        interpret = _interpret()
    if block_e is None:
        block_e = _pick_block(src.shape[0])
    return _intersect_jit(adj, src, dst, sentinel, block_e, interpret)
