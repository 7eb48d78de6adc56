"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (each module's docstring names
the paper artifact it maps to).  ``--json [PATH]`` additionally writes
every record (plus warm/cold trace counters from the runtime cache) to a
machine-readable file (default ``BENCH_fct.json``) so the perf trajectory is
comparable across PRs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# allow `python benchmarks/run.py ...` from anywhere: put the repo root (and
# src/, for when PYTHONPATH is unset) on sys.path before package imports
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("benchmark", nargs="?", default=None,
                    help="run a single benchmark module")
    ap.add_argument("--json", nargs="?", const="BENCH_fct.json", default=None,
                    metavar="PATH", help="write results as JSON")
    args = ap.parse_args()

    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    from benchmarks import (common, device_scaling, ingest_stream,
                            kernel_micro, multi_query, response_time,
                            serving_load, shares_comm, shuffle_size,
                            skew_adjust, topk_transfer)
    mods = {
        "response_time": response_time,
        "multi_query": multi_query,
        "serving_load": serving_load,
        "shuffle_size": shuffle_size,
        "skew_adjust": skew_adjust,
        "shares_comm": shares_comm,
        "kernel_micro": kernel_micro,
        # finalize transfer budget: full-histogram vs fct_topk d2h bytes,
        # plus the cross-CN-group pruning record; standalone merge-in
        # --json semantics and a --quick CI mode like device_scaling
        "topk_transfer": topk_transfer,
        # incremental ingest: appends interleaved with warm queries —
        # zero-retrace + chunk-only upload + within-2x first-query-after-
        # append records; standalone merge-in --json and --quick like
        # topk_transfer
        "ingest_stream": ingest_stream,
        # subprocess fan-out over forced device counts; also runnable
        # standalone (`python benchmarks/device_scaling.py`) with merge-in
        # --json semantics and a --quick CI mode
        "device_scaling": device_scaling,
    }
    if args.benchmark is not None and args.benchmark not in mods:
        ap.error(f"unknown benchmark {args.benchmark!r} "
                 f"(choose from {', '.join(mods)})")
    if args.json in mods and args.benchmark is None:
        # `--json kernel_micro` swallowed the benchmark name as the path
        ap.error(f"{args.json!r} looks like a benchmark name, not a JSON "
                 f"path — use `run.py {args.json} --json [PATH]`")
    print("name,us_per_call,derived")
    for name, mod in mods.items():
        if args.benchmark and args.benchmark != name:
            continue
        mod.run()

    if args.json:
        import jax
        # cold/warm trace counts live on the per-record "traces" fields
        # (each response_time config measures its own fresh-cache engine)
        payload = {
            "meta": {
                "backend": jax.default_backend(),
                "n_devices": len(jax.devices()),
                "jax": jax.__version__,
            },
            "benchmarks": common.RECORDS,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {len(common.RECORDS)} records to {args.json}")


if __name__ == "__main__":
    main()
