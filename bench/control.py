"""Readings for the limits that decide ``correct``; not part of a benchmark run.

    python bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

For each seed, one run of the cell as ``bench/run.py`` makes it (same data,
set-up, traffic and window), in one process, compared twice after the
window: the program's answers against the reference (the sound reading)
and the control's answers against the reference (the upper reading).  The
control is the reference put in the program's place with each CN's
histogram held in bfloat16: it breaks the configuration's exact-count
guarantee as a narrower accumulator would.  One JSON line per seed and
reading goes to standard output.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run  # bench/ is on sys.path when run as a script; run adds the root

from bench.reference.data import load_config


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = run.load_benchmark()
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(run.CACHE_DIR))
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    cfg, mix = load_config(cell["config"]), run.load_mix(cell["traffic"])
    for seed in args.seeds:
        res = run.run_cell(cfg, mix, chips=cell["chips"], seed=seed,
                           seconds=args.seconds, trace=False, metrics=[],
                           t_process=time.perf_counter(),
                           round_to="bfloat16", sound_too=True)
        for reading in ("program", "control"):
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "reading": reading,
                              "attempted": res["attempted"],
                              "checks": res[f"{reading}_checks"]}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
