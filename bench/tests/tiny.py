"""A copy of the benchmark at a size a CPU test can run: the same files,
with the graphs cut to scale 10 and the tiered pool to 2 of 4 shards."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

import jax

from bench import harness

REPO = Path(__file__).resolve().parents[2]
TINY = {"scale": 10, "nshards": 4, "resident_shards": 2}


def make_root(tmp: Path) -> Path:
    """A checkout-like directory under ``tmp`` whose configurations are
    cut to :data:`TINY`."""
    shutil.copytree(REPO / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for entry in bench["configs"]:
        path = tmp / entry["file"]
        config = json.loads(path.read_text())
        config.update({k: v for k, v in TINY.items() if k in config})
        path.write_text(json.dumps(config))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def cpu_devices(chips: int):
    """The harness's look for a chip, skipped: the CPU device."""
    return jax.devices()[:1]


def run(root: Path, cell: str, seed: int = 3, trace: bool = False) -> dict:
    return harness.run_cell(root, cell, seed, seconds=0.01, trace=trace,
                            chips_check=cpu_devices)
