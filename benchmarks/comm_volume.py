"""Communication-volume sweep — CVC vs full-mesh cross-device reduction.

The sharded engine's phase-2 label reduction is the analogue of Gluon's
mirror sync: the paper's cluster baseline scales to 256 hosts only because
CVC reduces along grid columns and gathers along rows instead of
all-reducing every mirror everywhere.  This suite sweeps the engine's
``CrossReducer`` modes over 1/2/4/8 forced host devices:

* ``oec``   — ``partition_1d`` shards, ``owner1d`` (owner-targeted
  reduce-scatter + gather) vs ``full`` (all-axis all-reduce);
* ``cvc2d`` — ``partition_2d`` (2, D/2) grids, column-reduce + row-gather
  vs ``full``.

Rows report the analytic reduction-volume model accumulated into
``RunStats`` (``comm_elems`` / ``comm_bytes`` / ``reduce_axis_hops`` — see
``sharded.CrossReducer.comm_per_relax`` for the convention) plus measured
wall time; labels are asserted bitwise identical between the reducers
before a row is emitted, so every number compares the *same* computation.
Adding devices should shrink the communication-avoiding share per device —
the ISSUE's "adding devices should remove communication, not add it".
"""

from __future__ import annotations

import textwrap

from .common import run_bench_subprocess

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    from jax.sharding import Mesh

    from repro.core import from_coo, shard_graph
    from repro.core.algorithms import bfs
    from repro.graphs import generators as gen

    src, dst, n = gen.rmat(10, 12, seed=1)
    g = from_coo(src, dst, n, block_size=512)
    source = int(np.argmax(np.bincount(src, minlength=n)))

    devs = np.array(jax.devices())

    def cells(d):
        yield "oec", Mesh(devs[:d].reshape(d), ("data",)), ("data",), {}
        if d >= 4:
            grid = (2, d // 2)
            yield ("cvc2d", Mesh(devs[:d].reshape(grid), ("data", "model")),
                   ("data", "model"), dict(scheme="cvc", grid=grid))

    for d in (1, 2, 4, 8):
        for scheme_name, mesh, axes, kw in cells(d):
            out = {}
            for reducer in ("cvc", "full"):
                sg = shard_graph(g, mesh, axes, policy="blocked",
                                 reducer=reducer, **kw)
                us = t(lambda: bfs.bfs_dd_sparse(sg, source)[0])
                labels, st = bfs.bfs_dd_sparse(sg, source)
                out[reducer] = (np.asarray(labels), st, us)
            assert np.array_equal(out["cvc"][0], out["full"][0]), \
                (scheme_name, d)
            ratio = (out["full"][1].comm_elems /
                     out["cvc"][1].comm_elems
                     if out["cvc"][1].comm_elems else 1.0)
            for reducer in ("cvc", "full"):
                _, st, us = out[reducer]
                name = f"comm/{scheme_name}_{reducer}_dev{d}"
                emit(name, us,
                     f"comm_elems={st.comm_elems};"
                     f"comm_bytes={st.comm_bytes};"
                     f"reduce_axis_hops={st.reduce_axis_hops};"
                     f"full_over_cvc={ratio:.2f}",
                     dict(st.as_dict(), wall_us=us, scheme=scheme_name,
                          reducer=reducer, full_over_cvc=ratio))
""")


def run():
    return run_bench_subprocess(_SCRIPT, "comm/ERROR")
