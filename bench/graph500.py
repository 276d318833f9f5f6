"""The Graph500 Kronecker generator, on the device, from a seed.

Follows the Graph500 specification's reference generator
(``kronecker_generator.m``): each of ``edge_factor * 2**scale`` edges picks
one quadrant per bit with initiator probabilities A, B, C (and
D = 1 - A - B - C), then every vertex label goes through one uniform random
permutation of ``[0, 2**scale)``.  The output keeps what the specification
keeps: duplicate edges and self-loops, as an edge *list*; whoever builds a
graph from it removes them.  The specification's final shuffle of the edge
order is left out: it changes no graph built from the list, and every
consumer here sorts the edges anyway.

The Kronecker draw (the graph's structure) comes from ``structure_seed``
and the relabelling from the run's ``seed``: every seed gets the same graph
up to the names of its vertices, so the same searches cost the same work,
on arrays that differ from seed to seed.  With ``relabel_block`` the
permutation itself is the structure's, and the seed only shuffles names
inside aligned blocks of that many ids: a container cut into ranges of
whole blocks then holds the same edges in every range for every seed.

The quadrant bits are drawn with ``jax.random`` in one jitted call, so a
scale-22 graph takes the device about a second instead of a minute of host
numpy.  The relabelling permutation (``2**scale`` entries) is drawn on the
host with numpy's Fisher-Yates shuffle from the same seed and applied on
the device: ``jax.random.permutation`` sorts, and a sort of 4M keys costs
the TPU compiler about a minute.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole ``seed``, also past 32 bits
    (the low word seeds the key, the high word is folded in)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def _rng(seed: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0x6500])


def relabelling(seed: int, n: int, structure_seed: int = 0,
                block: int | None = None) -> np.ndarray:
    """The permutation of ``[0, n)`` that relabels the vertices, as int32:
    uniform from ``seed``, or, with ``block``, uniform from
    ``structure_seed`` and then shuffled by ``seed`` within each aligned
    run of ``block`` ids (``n`` a multiple of ``block``)."""
    if block is None:
        return _rng(seed).permutation(n).astype(np.int32)
    if n % block:
        raise ValueError(f"n = {n} is not a multiple of block = {block}")
    base = _rng(structure_seed).permutation(n)
    within = _rng(seed).permuted(
        np.tile(np.arange(block), (n // block, 1)), axis=1)
    return (base - base % block + within[base // block, base % block]
            ).astype(np.int32)


@partial(jax.jit, static_argnames=("scale", "edge_factor", "a", "b", "c"))
def kronecker_edges(key, perm, *, scale: int, edge_factor: int, a: float,
                    b: float, c: float):
    """``(src, dst)`` int32 device arrays of ``edge_factor * 2**scale``
    Kronecker edges over ``2**scale`` vertices, relabelled through
    ``perm`` (or not, where ``perm`` is None)."""
    m = edge_factor << scale
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    bit_key = key

    def one_bit(ib, carry):
        i, j = carry
        ki, kj = jax.random.split(jax.random.fold_in(bit_key, ib))
        ii = jax.random.uniform(ki, (m,)) > ab
        jj = jax.random.uniform(kj, (m,)) > jnp.where(ii, c_norm, a_norm)
        i = i | (ii.astype(jnp.int32) << ib)
        j = j | (jj.astype(jnp.int32) << ib)
        return i, j

    zero = jnp.zeros((m,), jnp.int32)
    i, j = jax.lax.fori_loop(0, scale, one_bit, (zero, zero))
    if perm is not None:
        i, j = perm[i], perm[j]
    return i, j


def generate(seed: int, *, structure_seed: int, scale: int, edge_factor: int,
             a: float, b: float, c: float,
             relabel_block: int | None = None):
    """The edge list on the host: ``(src, dst, n, labels)``, int32 numpy
    arrays with duplicates and self-loops included, and ``labels[v]``
    the name that vertex ``v`` of the Kronecker draw was given."""
    n = 1 << scale
    labels = relabelling(seed, n, structure_seed, relabel_block)
    src, dst = kronecker_edges(seed_key(structure_seed), jnp.asarray(labels),
                               scale=scale, edge_factor=edge_factor, a=a, b=b,
                               c=c)
    src, dst = jax.device_get((src, dst))
    return np.asarray(src), np.asarray(dst), n, labels
