"""Paper Fig. 3 + §4.1 — allocation-policy study on a device mesh.

Local / interleaved / blocked placement of graph arrays over 8 host
devices (subprocess so the main bench process keeps 1 device).  Derived
columns report the per-device byte balance — the quantity that produced the
paper's 5.6×/39× cliffs (fast-tier overflow), which wall-time on a 1-core
container cannot show — plus wall time for completeness, and the §4.2
churn-model break-even (why migration stays off).
"""

from __future__ import annotations

import textwrap

from repro.core.placement import ChurnModel

from .common import row, run_bench_subprocess

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import time
    import numpy as np
    import jax, jax.numpy as jnp

    from repro.core import from_coo
    from repro.core import placement as pl
    from repro.core.algorithms import bfs
    from repro.graphs import generators as gen

    src, dst, n = gen.rmat(10, 12, seed=1)
    mesh = jax.sharding.Mesh(np.array(jax.devices()).reshape(8), ("data",))
    g = from_coo(src, dst, n, block_size=512)
    source = int(np.argmax(np.bincount(src, minlength=n)))

    for policy in ("local", "interleaved", "blocked"):
        gp = pl.place_graph(g, mesh, ("data",), policy)
        dist, _ = bfs.bfs_dd_dense(gp, source)   # warmup+compile
        t0 = time.perf_counter()
        dist, _ = bfs.bfs_dd_dense(gp, source)
        jax.block_until_ready(dist)
        us = (time.perf_counter() - t0) * 1e6
        # per-device byte balance of the edge arrays
        shard_bytes = [0] * 8
        for arr in (gp.col_idx, gp.src_idx, gp.edge_w):
            for sh in arr.addressable_shards:
                shard_bytes[sh.device.id] += sh.data.size * sh.data.dtype.itemsize
        mx, mn = max(shard_bytes), max(min(shard_bytes), 1)
        emit(f"fig3/bfs_{policy}", us,
             f"max_dev_bytes={mx};imbalance={mx/mn:.2f}")
""")


def run():
    rows = run_bench_subprocess(_SCRIPT, "fig3/ERROR", timeout=600)
    # §4.2 churn model: migrating 1 GB mid-run vs 10 µs/round locality gain
    cm = ChurnModel()
    be = cm.breakeven_rounds(1 << 30, 10e-6)
    rows.append(row("fig4/migration_breakeven_rounds", 0.0,
                    f"rounds={be:.0f};verdict=migration_off"))
    return rows
