"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (deliverable d).  With
``--emit-json`` each suite additionally persists its rows — including the
full ``RunStats.as_dict()`` per (algo, substrate, ndev) where the suite
collects one — to ``BENCH_<suite>.json`` (or an explicit path when a
single suite is selected), so the repo accumulates a perf trajectory
instead of throwing the numbers away with the process.

  memtier      Tables 1-2   memory-tier model + host write proxy
  placement    Fig 3/§4.1   local/interleaved/blocked placement (8 devices)
  granularity  Fig 4-5/§4.3 block-size ("page size") sweep + churn model
  algo_classes Fig 6-7/§5   algorithm classes × diameter regimes
  frameworks   Fig 8-9/§6.1 framework capability classes
  scaling      Fig 10/§6.2  strong scaling: sharded engine vs BSP baseline
  vs_cluster   Fig 11/§6.3  single machine vs BSP cluster engine
  comm_volume  §CVC         CVC vs full-mesh reduction volume, 1-8 devices
  outofcore    §Thesis      streamed shards vs all-resident pool (tiered)
  serving      §Serving     multi-source batched queries: amortization + QPS
  dynamic      §Dynamic     edge-log deltas: incremental vs full recompute
  kernels      —            Pallas kernel µs/call
"""

import argparse
import json
import sys
import traceback

from repro.compile_cache import enable_compile_cache

from . import (algo_classes, common, comm_volume, dynamic, frameworks,
               granularity, kernels_bench, memtier, outofcore, placement,
               scaling, serving, vs_cluster)

SUITES = {
    "memtier": memtier,
    "placement": placement,
    "granularity": granularity,
    "algo_classes": algo_classes,
    "frameworks": frameworks,
    "scaling": scaling,
    "vs_cluster": vs_cluster,
    "comm_volume": comm_volume,
    "outofcore": outofcore,
    "serving": serving,
    "dynamic": dynamic,
    "kernels": kernels_bench,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--suite", action="append", default=None,
                    help="subset of suites (default: all)")
    ap.add_argument("--emit-json", nargs="?", const="auto", default=None,
                    metavar="PATH",
                    help="persist rows (+ RunStats) as JSON: "
                         "BENCH_<suite>.json per suite, or PATH when "
                         "exactly one suite is selected")
    ap.add_argument("--list", action="store_true",
                    help="print available suite names and exit")
    args = ap.parse_args()
    if args.list:
        print("\n".join(SUITES))
        return
    enable_compile_cache()
    names = args.suite or list(SUITES)
    unknown = [n for n in names if n not in SUITES]
    if unknown:
        ap.error(f"unknown suite(s) {', '.join(unknown)}; "
                 f"available: {', '.join(SUITES)}")
    if args.emit_json not in (None, "auto") and len(names) != 1:
        ap.error("--emit-json PATH needs exactly one --suite "
                 "(omit PATH for per-suite BENCH_<suite>.json files)")
    print("name,us_per_call,derived")
    ok = True
    for name in names:
        try:
            rows = SUITES[name].run()
            common.print_rows(rows)
            # subprocess suites report a dead child as a */ERROR row; that
            # must fail the harness, not ship an empty trajectory
            if any(str(r[0]).endswith("/ERROR") for r in rows):
                ok = False
            if args.emit_json is not None:
                path = (f"BENCH_{name}.json" if args.emit_json == "auto"
                        else args.emit_json)
                with open(path, "w") as fh:
                    json.dump(common.rows_as_json(name, rows), fh, indent=1)
                print(f"# wrote {path}", file=sys.stderr)
        except Exception:  # noqa: BLE001
            ok = False
            print(f"{name}/SUITE_ERROR,0.0,", file=sys.stdout)
            traceback.print_exc()
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
