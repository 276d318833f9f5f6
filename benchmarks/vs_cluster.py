"""Paper Fig. 11 — single big-memory machine vs distributed cluster.

OB/OA/OS vs DB/DM/DS, re-staged on host devices (subprocess, 8 devices):

  OB  single-partition engine, best algorithm (pointer-jump CC, sparse BFS)
  OA  single-partition engine, vertex programs only
  DM  CVC-partitioned BSP vertex-program engine on 8 "hosts" (D-Galois class)

Derived columns carry the paper's actual argument: rounds × O(n) sync bytes
for the BSP engine vs zero communication for the shared-memory engine, and
the round-count gap between label-prop (diameter-bound) and pointer-jumping
(log n) — machine-size-independent quantities.
"""

from __future__ import annotations

import textwrap

from .common import run_bench_subprocess

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax

    from repro.core import from_coo, partition as pt
    from repro.core.algorithms import bfs, cc
    from repro.graphs import generators as gen

    src, dst, n = gen.web_crawl_like(24, 5, 10, 2, seed=2)
    g = from_coo(src, dst, n, block_size=512, symmetrize=True)
    s = np.asarray(g.src_idx)[:g.m]
    source = int(np.argmax(np.bincount(s, minlength=n)))

    # --- OB: best algorithms, single partition
    us = t(lambda: bfs.bfs_dd_sparse(g, source)[0])
    _, st = bfs.bfs_dd_sparse(g, source)
    emit("fig11/bfs/OB", us, f"rounds={st.rounds};sync_bytes=0")
    us = t(lambda: cc.cc_pointer_jump(g)[0])
    _, st = cc.cc_pointer_jump(g)
    emit("fig11/cc/OB", us, f"rounds={st.rounds};sync_bytes=0")

    # --- OA: vertex programs, single partition
    us = t(lambda: bfs.bfs_dd_dense(g, source)[0])
    _, st = bfs.bfs_dd_dense(g, source)
    emit("fig11/bfs/OA", us, f"rounds={st.rounds};sync_bytes=0")
    us = t(lambda: cc.cc_labelprop(g)[0])
    _, st = cc.cc_labelprop(g)
    emit("fig11/cc/OA", us, f"rounds={st.rounds};sync_bytes=0")

    # --- DM: CVC-partitioned BSP vertex programs on 8 hosts
    mesh = jax.sharding.Mesh(np.array(jax.devices()).reshape(4, 2),
                             ("data", "model"))
    pg = pt.partition_2d(g, 4, 2)
    label_bytes = 4 * g.n_pad  # one dense label sync per round per device
    us = t(lambda: pt.bsp_bfs(pg, mesh, ("data", "model"), source)[0])
    _, rounds = pt.bsp_bfs(pg, mesh, ("data", "model"), source)
    emit("fig11/bfs/DM", us, f"rounds={rounds};"
         f"sync_bytes={rounds*label_bytes*8}")
    us = t(lambda: pt.bsp_cc(pg, mesh, ("data", "model"))[0])
    _, rounds = pt.bsp_cc(pg, mesh, ("data", "model"))
    emit("fig11/cc/DM", us, f"rounds={rounds};"
         f"sync_bytes={rounds*label_bytes*8}")
""")


def run():
    return run_bench_subprocess(_SCRIPT, "fig11/ERROR")
