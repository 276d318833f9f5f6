"""The ``tiered`` container: the graph's edge shards in host memory behind
a device pool of ``resident_shards`` of its ``nshards`` shards
(``tier_graph``); only the vertex arrays stay on the device.  The graph
is undirected (Graph500), so its symmetric CSR doubles as its CSC."""

import jax

from repro.core import from_coo
from repro.core.tiered import tier_graph


def build(src, dst, n: int, config: dict):
    g = from_coo(src, dst, n, symmetrize=True, build_csc=True)
    tg = tier_graph(g, nshards=config["nshards"],
                    resident_shards=config["resident_shards"],
                    build_csc=True)
    del g
    jax.block_until_ready((tg.out_deg, tg.owner))
    return tg
