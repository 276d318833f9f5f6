"""The ``torus3d`` generator: Ligra's ``3D-grid``, a ``side``**3 torus.

Vertex ``x*L**2 + y*L + z`` (Ligra's row-major order, ``L = side``) is
joined to ``x+1``, ``y+1`` and ``z+1`` mod ``L``, so each of the ``3*L**3``
undirected edges appears once and every vertex has six neighbours.  The
structure does not depend on any seed; the run's seed only shuffles the
vertex names within aligned blocks of ``relabel_block`` ids, with no
global permutation, so every seed's graph is the same up to names that
stay inside their block.
"""

import numpy as np

from bench.harness import EdgeList, seed_rng

LABELS_TAG = 0x7D3


def labels(seed: int, n: int, block: int) -> np.ndarray:
    """``labels[v]``: the name vertex ``v`` gets in this run, a uniform
    shuffle (from ``seed``) of each aligned run of ``block`` ids."""
    if n % block:
        raise ValueError(f"n = {n} is not a multiple of block = {block}")
    within = seed_rng(seed, LABELS_TAG).permuted(
        np.tile(np.arange(block, dtype=np.int32), (n // block, 1)), axis=1)
    return (np.arange(n, dtype=np.int32) // block * block
            + within.reshape(-1))


def torus_edges(side: int):
    """``(src, dst)`` int32 in structural names: one ``+1`` edge per
    vertex and dimension, wrapping at ``side``."""
    if side < 3:
        raise ValueError(f"side {side} < 3 has repeated or self edges")
    v = np.arange(side ** 3, dtype=np.int32)
    x, rest = np.divmod(v, side * side)
    y, z = np.divmod(rest, side)
    plus = lambda c: (c + 1) % side
    dst = np.concatenate([(plus(x) * side + y) * side + z,
                          (x * side + plus(y)) * side + z,
                          (x * side + y) * side + plus(z)])
    return np.tile(v, 3), dst.astype(np.int32)


def generate(seed: int, config: dict) -> EdgeList:
    side = int(config["side"])
    n = side ** 3
    names = labels(seed, n, int(config["relabel_block"]))
    src, dst = torus_edges(side)
    return EdgeList(src=names[src], dst=names[dst], n=n, labels=names,
                    structure_seed=config["structure_seed"])
