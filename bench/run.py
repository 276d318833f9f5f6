#!/usr/bin/env python3
"""Run one benchmark cell once on the chip(s) of this machine.

    python3 bench/run.py --workload g500-22.bfs --seed 7 --seconds 10 \\
        --trace 0

The last line of standard output is the result: one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and,
with ``--trace 1``, ``breakdown``), ending with ``checks``: every number
compared with the plain reference beside its limit.  The same numbers are
the last lines of standard error.  Without a TPU, or with fewer chips than
the cell asks for, it exits with code 3 and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

# libtpu would otherwise keep its logs under a fixed /tmp path, outside
# the checkout and the run's own directories
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = Path(__file__).resolve().parents[1]
# the checkout's root, not bench/, heads the path: bench's modules must
# not shadow the standard library's
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True,
                    help="a cell's name in BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: trace the window and report per-layer metrics")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    try:
        result = harness.run_cell(ROOT, args.workload, args.seed,
                                  args.seconds, bool(args.trace))
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
