"""Hop distances on a ``side``**3 torus, in closed form.

A plain reference for the ``torus3d`` graph, written from its definition
with numpy alone: it imports nothing of the program and builds no graph.
On a torus the shortest path moves independently in each dimension, each
the shorter way round its ring, so the hop distance from ``r`` to ``a`` is
``sum_d min(|a_d - r_d|, L - |a_d - r_d|)``.  Vertices are in the
generator's structural (row-major) names, ``x*L**2 + y*L + z``.
"""

from __future__ import annotations

import numpy as np


def torus_hops(side: int, root: int) -> np.ndarray:
    """(side**3,) int32 hop distance from structural vertex ``root`` to
    every structural vertex."""
    rx, rest = divmod(int(root), side * side)
    ry, rz = divmod(rest, side)
    ring = np.arange(side, dtype=np.int32)
    hx, hy, hz = (np.minimum(abs(ring - r), side - abs(ring - r))
                  for r in (rx, ry, rz))
    return (hx[:, None, None] + hy[None, :, None]
            + hz[None, None, :]).reshape(-1)
