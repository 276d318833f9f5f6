"""Runs one cell of ``BENCHMARK.json`` once and prints its result line.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by name under ``<root>/bench``:

* ``configs/<config>.json``     the deployment: graph, container, source;
* ``workloads/<traffic>.json``  the traffic mix: which job, its sizes;
* ``generators/<name>.py``      makes the edge list from the seed;
* ``containers/<name>.py``      hands the edge list to the program;
* ``jobs/<name>.py``            drives the program's timed path, warms it
                                up and checks it against the reference;
* ``metrics/<metric>.py``       reads one metric from the run.

A new cell, configuration or metric is a new file and a new entry in
``BENCHMARK.json``; no existing file changes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Optional

import numpy as np

from . import trace_reduce

DATA_KINDS = {"configs": ".json", "workloads": ".json"}


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# Finding things by name
# ---------------------------------------------------------------------------


def bench_dir(root: Path) -> Path:
    return Path(root) / "bench"


def load_benchmark(root: Path) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def list_names(root: Path, kind: str) -> list[str]:
    """Names of the ``kind`` files under ``<root>/bench/<kind>``."""
    suffix = DATA_KINDS.get(kind, ".py")
    d = bench_dir(root) / kind
    if not d.is_dir():
        return []
    return sorted(p.name[: -len(suffix)] for p in d.iterdir()
                  if p.name.endswith(suffix) and not p.name.startswith("_"))


def load_data(root: Path, kind: str, name: str) -> dict:
    """The JSON object of ``<root>/bench/<kind>/<name>.json``."""
    path = bench_dir(root) / kind / f"{name}{DATA_KINDS[kind]}"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    with open(path) as f:
        return json.load(f)


def load_plugin(root: Path, kind: str, name: str):
    """The module ``<root>/bench/<kind>/<name>.py``, loaded from its file
    (names may hold ``.`` and ``-``, which import statements cannot)."""
    path = bench_dir(root) / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module for {name!r}: {path}")
    modname = "bench_plugin_" + re.sub(r"\W", "_", f"{kind}_{name}_{path}")
    mod = sys.modules.get(modname)
    if mod is None:
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[modname] = mod
        spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[c['name'] for c in bench['workloads']]}")


def cell_config(root: Path, bench: dict, cell: dict) -> dict:
    """The configuration file of ``cell``, as named in BENCHMARK.json."""
    for entry in bench["configs"]:
        if entry["name"] == cell["config"]:
            path = Path(root) / entry["file"]
            with open(path) as f:
                return json.load(f)
    raise KeyError(f"cell {cell['name']!r} names config {cell['config']!r}, "
                   "which BENCHMARK.json does not list")


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics
    without a trace, its per-layer metrics with one.  A metric without a
    ``workloads`` key belongs to every cell (a per-layer one: to every
    cell that reports the end-to-end metric it moves)."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EdgeList:
    """What a generator hands over: the edge list (``src[i] -> dst[i]``
    over ``n`` vertices, as generated), and for each vertex of the
    seed-independent structure the name this run gave it (``labels``),
    so that a job can pick the same structural vertices in every run."""

    src: np.ndarray
    dst: np.ndarray
    n: int
    labels: np.ndarray
    structure_seed: int


@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers read it."""

    cell: str
    config: dict
    traffic: dict
    seed: int
    seconds: float
    setup_s: float = 0.0
    setup_compile_s: float = 0.0
    window_compiles: int = 0
    jobs: list = dataclasses.field(default_factory=list)
    trace: Any = None              # trace_reduce.TraceSummary when traced
    peaks: Optional[dict] = None
    extra: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Check:
    """One number compared with the reference, and its limit: the run is
    correct only where ``value <= limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


def require_devices(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    return devices[:chips]


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at ``<root>/.jax_cache``, a
    fixed path inside the checkout, unless ``JAX_COMPILATION_CACHE_DIR``
    names one.  Every program is cached, however fast it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(Path(root) / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def seed_rng(seed: int, tag: int) -> np.random.Generator:
    """A numpy generator for one purpose (``tag``) of the run's seed."""
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, tag])


def process_age_s() -> float:
    """Seconds since this process started (interpreter start-up
    included)."""
    import psutil
    return time.time() - psutil.Process().create_time()


def progress(msg: str) -> None:
    print(f"bench: [{process_age_s():7.1f} s] {msg}", file=sys.stderr,
          flush=True)


def run_cell(root: Path, cell_name: str, seed: int, seconds: float,
             trace: bool, chips_check=require_devices) -> dict:
    """Set up, measure for ``seconds``, check and reduce; return the
    result line's object."""
    from .phase_clock import CompileClock

    root = Path(root)
    bench = load_benchmark(root)
    cell = find_cell(bench, cell_name)
    config = cell_config(root, bench, cell)
    traffic = load_data(root, "workloads", cell["traffic"])
    devices = chips_check(cell["chips"])
    device = devices[0]
    enable_compile_cache(root)
    clock = CompileClock()
    run = Run(cell=cell_name, config=config, traffic=traffic, seed=seed,
              seconds=seconds)
    try:
        if device.platform == "tpu":
            from .peaks import peaks
            run.peaks = peaks(device.device_kind)
        gen = load_plugin(root, "generators", config["generator"])
        progress(f"{cell_name}: generating {config['generator']} "
                 f"(seed {seed})")
        edges = gen.generate(seed, config)
        container = load_plugin(root, "containers", config["container"])
        progress(f"{cell_name}: building the {config['container']} "
                 f"container over {len(edges.src)} generated edges")
        graph = container.build(edges.src, edges.dst, edges.n, config)
        job_mod = load_plugin(root, "jobs", traffic["job"])
        job = job_mod.Job(graph, edges, traffic, seed, run)
        progress(f"{cell_name}: warming up")
        job.warm_up()
        run.setup_s = process_age_s()
        run.setup_compile_s = clock.compile_s
        progress(f"{cell_name}: set-up {run.setup_s:.2f} s "
                 f"({run.setup_compile_s:.2f} s of it compiling); "
                 f"window of {seconds} s")

        setup_stats = device.memory_stats() or {}
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
        compiles0 = clock.backend_compiles
        if trace:
            trace_reduce.start_trace(trace_dir)
        with (trace_reduce.window_annotation() if trace
              else contextlib.nullcontext()):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                run.jobs.append(job.run_one())
            t1 = time.perf_counter()
        if trace:
            trace_reduce.stop_trace()
        run.window_compiles = clock.backend_compiles - compiles0
        stats = device.memory_stats() or {}
        memory_peak = int(stats.get("peak_bytes_in_use", 0))
        # the process's peak may date from set-up (a graph built whole on
        # the device before its tier cut); these two say what the window
        # itself held
        memory = {"setup_peak_bytes":
                  int(setup_stats.get("peak_bytes_in_use", 0)),
                  "window_end_bytes_in_use":
                  int(stats.get("bytes_in_use", 0))}
        progress(f"{cell_name}: window {t1 - t0:.2f} s, "
                 f"{len(run.jobs)} jobs, {run.window_compiles} compiles "
                 "inside it")

        if trace:
            run.trace = trace_reduce.reduce_trace(trace_dir)
            shutil.rmtree(trace_dir, ignore_errors=True)

        # the program's state goes before the reference runs
        job.collect()
        del graph
        progress(f"{cell_name}: checking against the reference")
        checks = job.check(edges)
    finally:
        clock.close()

    metrics = {}
    for m in cell_metrics(bench, cell_name, trace):
        value = load_plugin(root, "metrics", m["name"]).read(run)
        if value is None:
            continue
        if not np.isfinite(value):
            progress(f"{cell_name}: {m['name']} read {value}; left out")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    failed = sum(1 for j in run.jobs if j.get("correct") is False)
    correct = all(c.ok for c in checks) and failed == 0 and bool(run.jobs)
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(devices), "memory_peak_bytes": memory_peak,
           **memory}
    result = {"correct": correct, "attempted": len(run.jobs),
              "failed": failed, "metrics": metrics, "device": dev}
    if run.trace is not None:
        dev["busy_s"] = run.trace.busy_s
        dev["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
    result["window_compiles"] = run.window_compiles
    result["setup_compile_s"] = run.setup_compile_s
    for c in checks:
        print(f"check: {c.name} = {c.value!r}, limit {c.limit!r}: "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    result["checks"] = {c.name: {"value": _plain(c.value),
                                 "limit": _plain(c.limit)} for c in checks}
    return result


def _plain(x):
    """A number for the result line: JSON has no infinity or NaN."""
    return float(x) if np.isfinite(x) else str(x)
