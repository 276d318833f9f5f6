"""Shared benchmark utilities: timing, CSV rows, standard test graphs."""

from __future__ import annotations

import json
import subprocess
import sys
import textwrap
import time
from typing import Callable

import jax
import numpy as np

# Helper prelude injected into every ``run_bench_subprocess`` script, so the
# timing convention and the ROW/STAT emission protocol are defined once, not
# copy-pasted per suite.  Imports are function-local on purpose: the prelude
# is prepended *before* the script sets XLA_FLAGS, and jax must not be
# imported until after that.
#
# ``t`` runs one untimed warmup (compile) call and then ``reps`` timed
# calls, returning the **median** µs; the sorted samples are kept on
# ``t.samples`` so ``emit`` can record min/median/repeat-count alongside
# the row's RunStats.  Single-sample rows made the CI wall-clock ratio
# gate (benchmarks/ci_gate.py) hostage to one scheduler hiccup on a
# shared runner — the gate prefers ``wall_us_min`` (the least-interfered
# sample) and falls back to the median ``us_per_call``.
SUBPROC_HELPERS = textwrap.dedent("""
    def t(fn, reps=3):
        import time, jax
        fn()
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter(); out = fn()
            jax.block_until_ready(out)
            ts.append((time.perf_counter()-t0)*1e6)
        ts.sort()
        t.samples = ts
        return ts[len(ts) // 2]

    def emit(name, us, derived, stats=None):
        import json, jax
        print(f"ROW,{name},{us:.1f},{derived}")
        dev = jax.devices()[0]
        stats = dict(stats or {}, platform=dev.platform,
                     device_kind=dev.device_kind)
        samples = getattr(t, "samples", None)
        if samples:
            stats = dict(stats, wall_us_min=samples[0],
                         wall_us_median=samples[len(samples) // 2],
                         wall_us_reps=len(samples))
        print("STAT," + name + "," + json.dumps(stats))
""")


def time_call(fn: Callable, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall time in microseconds (blocks on jax outputs)."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))


def device_stats() -> dict:
    """``platform`` and ``device_kind`` of this process's first JAX device,
    so that a row measured on the CPU can never pass for a chip row."""
    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind}


def row(name: str, us: float, derived: str = "", stats: dict | None = None) -> tuple:
    """One benchmark row measured in this process.  ``stats`` (e.g.
    ``RunStats.as_dict()``) rides along for ``run.py --emit-json``, with
    this process's device added; the CSV printer ignores it."""
    return (name, us, derived, dict(stats or {}, **device_stats()))


def print_rows(rows):
    for r in rows:
        name, us, derived = r[0], r[1], r[2]
        print(f"{name},{us:.1f},{derived}")


def run_bench_subprocess(script: str, error_name: str, timeout: int = 900):
    """Run a benchmark script in a fresh interpreter (suites that force a
    host device count need one) and parse its ``ROW,name,us,derived`` /
    ``STAT,name,<json>`` protocol into row tuples.  The ``SUBPROC_HELPERS``
    prelude (``t``/``emit``) is prepended to every script.  Emits a single
    ``<error_name>,0.0,<stderr tail>`` row when the script produced
    nothing — ``run.py`` treats ``*/ERROR`` rows as suite failure.  Each
    row keeps the stats its child printed, which name the child's device
    (``JAX_PLATFORMS=cpu`` pins it to the CPU even on a chip host)."""
    r = subprocess.run(
        [sys.executable, "-c", SUBPROC_HELPERS + script],
        capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
             "JAX_PLATFORMS": "cpu"},
        timeout=timeout,
    )
    stats = {}
    for line in r.stdout.splitlines():
        if line.startswith("STAT,"):
            _, name, payload = line.split(",", 2)
            stats[name] = json.loads(payload)
    rows = []
    for line in r.stdout.splitlines():
        if line.startswith("ROW,"):
            _, name, us, derived = line.split(",", 3)
            rows.append((name, float(us), derived, stats[name]))
    if not rows:
        rows.append(row(error_name, 0.0,
                        r.stderr[-200:].replace(",", ";").replace("\n", " ")))
    return rows


def rows_as_json(suite: str, rows) -> dict:
    """JSON document for ``run.py --emit-json``: every row's name, wall
    time, derived counters, and the full stats dict when present."""
    out = []
    for r in rows:
        name, us, derived = r[0], r[1], r[2]
        stats = r[3] if len(r) > 3 else None
        entry = {"name": name, "us_per_call": us, "derived": derived}
        if stats is not None:
            entry["stats"] = stats
        out.append(entry)
    return {"suite": suite, "rows": out}


def bench_graphs(scale: str = "small"):
    """The Table-3 contrast pair at benchmark scale: low-diameter rmat vs
    high-diameter web-crawl-like."""
    from repro.graphs import generators as gen

    if scale == "small":
        return {
            "rmat": gen.rmat(10, 12, seed=1),
            "web": gen.web_crawl_like(24, 5, 10, 2, seed=2),
        }
    return {
        "rmat": gen.rmat(13, 16, seed=1),
        "web": gen.web_crawl_like(64, 6, 12, 2, seed=2),
    }
