"""The ``resident`` container: the whole graph in device memory, built by
the program's ``from_coo`` from the generated edge list.  The graph is
undirected (Graph500), so its symmetric CSR doubles as its CSC."""

import jax

from repro.core import from_coo


def build(src, dst, n: int, config: dict):
    g = from_coo(src, dst, n, symmetrize=True, build_csc=True)
    return jax.block_until_ready(g)
