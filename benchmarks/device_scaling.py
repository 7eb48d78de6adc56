"""Speedup-vs-devices curves — the paper's central parallel-scalability claim
(Fig. 6-8 vary workers; our §6 analogue varies simulated host devices).

Every other number in BENCH_fct.json was measured at ``n_devices=1``, where
the stacked-CN vmap, the ``P("w")`` store sharding and the reduce-scatter
aggregation are all structurally inert.  This driver spawns one subprocess
per device count with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
and measures, per N:

  * cold / warm single-query latency (adaptive-rho session, reduce-scatter),
  * an 8-query ``query_batch`` serving-load proxy (stacked per-CN dispatches),
  * plan shuffle volume (rows/bytes grow with over-decomposition — the
    Afrati-Ullman replication cost the balance pass trades for balance),
  * the dominant CN's ACHIEVED per-device row imbalance under the adaptive
    balance pass vs the fixed ``rho=4`` config point,
  * a bit-exactness hash of ``all_freqs`` — compared across ALL device
    counts and across psum vs reduce-scatter aggregation, under both accum
    policies (int32-checked subprocesses and ``JAX_ENABLE_X64=1`` ones).

Timing methodology.  Forced host "devices" are threads time-sharing this
machine's physical cores — on a single-core host the wall clock of an
N-device program is the SUM of all devices' work plus collective overhead,
not the parallel time a real N-device mesh would see.  Both numbers are
recorded, labeled:

  * ``wall_us`` — wall clock of the real N-thread-device program here;
  * ``us_per_call`` / ``speedup_vs_1dev`` — CRITICAL-PATH latency: fact
    rows are partitioned into N shards SIZED BY the adaptive plan's actual
    per-device row assignment for the dominant CN (the device program is
    dense — its cost depends on padded row counts, not row identity, so a
    shard with the hot device's row count costs what the hot device costs),
    each shard's full query runs warm on a 1-device mesh, and the parallel
    time is the slowest shard.  FCT histograms are additive over fact rows
    (every joined star tree is anchored at exactly one fact row), and the
    worker ASSERTS the shard histograms sum bit-exactly to the N-device
    result — so the shards really are a partition of the device's work.
    This excludes interconnect cost, which thread-devices cannot model
    faithfully anyway; the reduce-scatter exists to shrink exactly that.

The driver is self-checking: results must be bit-identical everywhere, and
(full mode) critical-path warm speedup at the largest N must exceed 1x
while the adaptive row imbalance must not regress the fixed-rho baseline.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

DEVICE_COUNTS = (1, 2, 4, 8)
QUICK_COUNTS = (1, 2)


# ---------------------------------------------------------------------------
# worker: runs in a subprocess whose XLA_FLAGS force the device count
# ---------------------------------------------------------------------------

def _worker(n_devices: int, quick: bool) -> None:
    import warnings
    warnings.filterwarnings("ignore")
    import hashlib
    import time

    import jax
    import numpy as np

    from benchmarks.common import make_dataset, timed
    from repro.api import FCTRequest, FCTSession, SessionConfig
    from repro.runtime.cache import ExecutableCache
    from repro.runtime.engine import FCTEngine

    assert len(jax.devices()) == n_devices, (
        f"XLA gave {len(jax.devices())} devices, wanted {n_devices}")
    x64 = bool(jax.config.jax_enable_x64)
    # x64 subprocesses only establish bit-exactness; keep them light
    scale = 1.0 if (quick or x64) else 4.0
    iters = 1 if quick else 3
    schema, kws = make_dataset(scale=scale, skew=1.2)
    req = FCTRequest(keywords=tuple(kws), r_max=4)

    engine = FCTEngine(cache=ExecutableCache())
    session = FCTSession(schema, engine=engine,
                         config=SessionConfig(adaptive_rho=True))
    t0 = time.perf_counter()
    resp = session.query(req)
    cold_us = (time.perf_counter() - t0) * 1e6
    out = {
        "n_devices": n_devices,
        "accum": resp.accum_policy,
        "scale": scale,
        "cold_us": round(cold_us, 1),
        "cold_traces": engine.cache.traces,
        "shuffle_rows": resp.shuffle_rows,
        "shuffle_bytes": resp.shuffle_bytes,
        "row_imbalance": round(resp.row_imbalance, 4),
        "hash": hashlib.sha256(
            np.ascontiguousarray(resp.all_freqs).tobytes()).hexdigest(),
    }
    traces = engine.cache.traces
    out["warm_us"] = round(timed(lambda: session.query(req),
                                 warmup=1, iters=iters), 1)
    out["warm_traces"] = engine.cache.traces - traces

    # fixed-rho=4 config point (the pre-balance-pass behavior) for the
    # before/after imbalance and replication numbers
    resp4 = session.query(FCTRequest(keywords=tuple(kws), r_max=4,
                                     mode="skew", rho=4))
    out["row_imbalance_rho4"] = round(resp4.row_imbalance, 4)
    out["shuffle_bytes_rho4"] = resp4.shuffle_bytes
    assert np.array_equal(resp4.all_freqs, resp.all_freqs), \
        "fixed-rho result diverged from adaptive"

    # serving-load proxy: 8 distinct (salted) requests through one
    # query_batch — same-signature CNs of different queries share stacked
    # per-CN dispatches, the multi-device payoff the batcher claims
    batch = [FCTRequest(keywords=tuple(kws), r_max=4, salt=s)
             for s in range(8)]
    session.query_batch(batch)  # compile the per-CN program family
    out["batch8_us"] = round(timed(lambda: session.query_batch(batch),
                                   warmup=1, iters=iters), 1)

    # critical-path simulation (see module docstring): fact-row shards
    # sized by the adaptive plan's ACTUAL per-device row assignment for the
    # dominant CN, each run warm on a 1-device mesh; slowest shard =
    # parallel latency minus interconnect
    from repro.core.candidate_network import (TupleSets, enumerate_star_cns,
                                              prune_empty_cns)
    from repro.core.plan import build_cn_plan
    from repro.data.schema import StarSchema
    from repro.launch.mesh import make_worker_mesh
    ts = TupleSets.build(schema, kws)
    cns = prune_empty_cns(enumerate_star_cns(len(kws), schema.m, 4), ts)
    dominant = max((cn for cn in cns if ts.cn_rows(cn)[0] is not None
                    and ts.cn_rows(cn)[1]),
                   key=lambda cn: len(ts.cn_rows(cn)[0]))
    dom_plan = build_cn_plan(schema, ts, dominant, n_devices,
                             mode="adaptive")
    load = dom_plan.device_rows.astype(np.float64)
    bounds = np.concatenate(
        [[0], np.round(np.cumsum(load / load.sum())
                       * schema.fact.rows)]).astype(int)
    bounds[-1] = schema.fact.rows
    shard_engine = FCTEngine(cache=ExecutableCache())
    mesh1 = make_worker_mesh(1)
    shard_warm, shard_batch, freq_sum = [], [], None
    for d in range(n_devices):
        if bounds[d + 1] == bounds[d]:
            continue  # idle device: contributes neither rows nor time
        shard = StarSchema(
            fact=schema.fact.take(np.arange(bounds[d], bounds[d + 1])),
            dims=schema.dims, edges=schema.edges,
            vocab_size=schema.vocab_size)
        s = FCTSession(shard, engine=shard_engine, mesh=mesh1,
                       config=SessionConfig(adaptive_rho=True))
        part = s.query(req).all_freqs.astype(np.int64)
        freq_sum = part if freq_sum is None else freq_sum + part
        shard_warm.append(timed(lambda s=s: s.query(req),
                                warmup=1, iters=iters))
        s.query_batch(batch)
        shard_batch.append(timed(lambda s=s: s.query_batch(batch),
                                 warmup=1, iters=iters))
    assert np.array_equal(freq_sum, resp.all_freqs.astype(np.int64)), \
        "fact-row shards do not sum to the full histogram"
    out["warm_critical_us"] = round(max(shard_warm), 1)
    out["batch8_critical_us"] = round(max(shard_batch), 1)

    # psum baseline must be bit-identical to the reduce-scatter path
    psum_session = FCTSession(
        schema, engine=FCTEngine(cache=ExecutableCache(),
                                 reduce_scatter=False),
        config=SessionConfig(adaptive_rho=True))
    out["rs_equals_psum"] = bool(
        np.array_equal(psum_session.query(req).all_freqs, resp.all_freqs))
    print("RESULT" + json.dumps(out), flush=True)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _spawn(n_devices: int, quick: bool, x64: bool) -> dict:
    env = dict(os.environ)
    # the workers simulate a mesh with host devices: pin them to the CPU so
    # they never reach for an accelerator the parent process already holds
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env.pop("JAX_ENABLE_X64", None)
    if x64:
        env["JAX_ENABLE_X64"] = "1"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(_ROOT, "src"), _ROOT,
                    env.get("PYTHONPATH")) if p)
    cmd = [sys.executable, os.path.abspath(__file__),
           "--worker", str(n_devices)]
    if quick:
        cmd.append("--quick")
    proc = subprocess.run(cmd, env=env, cwd=_ROOT, capture_output=True,
                          text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(
            f"device_scaling worker n={n_devices} x64={x64} failed:\n"
            f"{proc.stdout[-2000:]}\n{proc.stderr[-4000:]}")
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


def run(quick: bool = False) -> list:
    from benchmarks.common import emit

    counts = QUICK_COUNTS if quick else DEVICE_COUNTS
    results = {n: _spawn(n, quick, x64=False) for n in counts}
    x64_results = {n: _spawn(n, quick, x64=True) for n in counts}
    base = results[counts[0]]

    for n in counts:
        r = results[n]
        mesh = {"w": n}
        cold_speedup = round(base["cold_us"] / max(r["cold_us"], 1e-9), 2)
        emit(f"device_scaling/cold/n{n}", r["cold_us"],
             f"traces={r['cold_traces']} wall_speedup={cold_speedup} "
             "(wall clock; compile does not parallelize over thread-devices)",
             n_devices=n, mesh=mesh, kind="cold", traces=r["cold_traces"],
             wall_speedup_vs_1dev=cold_speedup, scale=r["scale"])
        warm_speedup = round(base["warm_critical_us"]
                             / max(r["warm_critical_us"], 1e-9), 2)
        wall_speedup = round(base["warm_us"] / max(r["warm_us"], 1e-9), 2)
        emit(f"device_scaling/warm/n{n}", r["warm_critical_us"],
             f"speedup_vs_1dev={warm_speedup} (critical path, plan-"
             f"proportional shards) wall_us={r['warm_us']} "
             f"wall_speedup={wall_speedup} new_traces={r['warm_traces']}",
             n_devices=n, mesh=mesh, kind="warm", traces=r["warm_traces"],
             speedup_vs_1dev=warm_speedup, wall_us=r["warm_us"],
             wall_speedup_vs_1dev=wall_speedup, scale=r["scale"])
        batch_speedup = round(base["batch8_critical_us"]
                              / max(r["batch8_critical_us"], 1e-9), 2)
        emit(f"device_scaling/serving_batch8/n{n}", r["batch8_critical_us"],
             f"speedup_vs_1dev={batch_speedup} (critical path; 8 salted "
             f"queries, stacked per-CN dispatches) wall_us={r['batch8_us']}",
             n_devices=n, mesh=mesh, speedup_vs_1dev=batch_speedup,
             wall_us=r["batch8_us"], scale=r["scale"])
        emit(f"device_scaling/shuffle/n{n}", float(r["shuffle_bytes"]),
             f"rows={r['shuffle_rows']} bytes_rho4={r['shuffle_bytes_rho4']} "
             "(adaptive over-decomposition buys balance with replication)",
             n_devices=n, mesh=mesh, shuffle_rows=r["shuffle_rows"],
             shuffle_bytes_rho4=r["shuffle_bytes_rho4"])
        emit(f"device_scaling/imbalance/n{n}", r["row_imbalance"],
             f"adaptive={r['row_imbalance']} "
             f"fixed_rho4={r['row_imbalance_rho4']} (dominant CN per-device "
             "fact rows, max/mean)", n_devices=n, mesh=mesh,
             row_imbalance=r["row_imbalance"],
             row_imbalance_rho4=r["row_imbalance_rho4"])

    bitexact_int32 = all(r["hash"] == base["hash"] for r in results.values())
    x64_base = x64_results[counts[0]]
    bitexact_int64 = all(r["hash"] == x64_base["hash"]
                         for r in x64_results.values())
    rs_ok = all(r["rs_equals_psum"]
                for r in list(results.values()) + list(x64_results.values()))
    emit("device_scaling/equivalence", 0.0,
         f"bitexact_int32={bitexact_int32} bitexact_int64={bitexact_int64} "
         f"rs_equals_psum={rs_ok} across n_devices={list(counts)}",
         n_devices=max(counts), mesh={"w": max(counts)},
         bitexact_int32=bitexact_int32, bitexact_int64=bitexact_int64,
         rs_equals_psum=rs_ok, device_counts=list(counts))

    assert bitexact_int32, "int32 results differ across device counts"
    assert bitexact_int64, "int64 (x64) results differ across device counts"
    assert rs_ok, "reduce-scatter diverged from psum"
    n_max = max(counts)
    if not quick:
        warm_speedup = (base["warm_critical_us"]
                        / max(results[n_max]["warm_critical_us"], 1e-9))
        assert warm_speedup > 1.0, (
            f"warm query does not scale: {warm_speedup:.2f}x at {n_max} "
            "devices")
        assert (results[n_max]["row_imbalance"]
                <= results[n_max]["row_imbalance_rho4"] + 1e-9), (
            "adaptive rho regressed the fixed-rho=4 row imbalance")
    return [results, x64_results]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", type=int, default=None, metavar="N",
                    help=argparse.SUPPRESS)  # internal: subprocess mode
    ap.add_argument("--quick", action="store_true",
                    help="CI mode: device counts (1, 2), scale 1, one iter")
    ap.add_argument("--no-json", action="store_true",
                    help="don't merge records into the JSON file")
    ap.add_argument("--json", default="BENCH_fct.json", metavar="PATH",
                    help="merge device_scaling records into PATH")
    args = ap.parse_args()
    if args.worker is not None:
        _worker(args.worker, args.quick)
        return

    from benchmarks.common import RECORDS
    run(quick=args.quick)
    if args.no_json:
        return
    # merge: replace any previous device_scaling records, keep the rest
    path = os.path.join(_ROOT, args.json) \
        if not os.path.isabs(args.json) else args.json
    try:
        with open(path) as f:
            payload = json.load(f)
    except (OSError, ValueError):
        import jax
        payload = {"meta": {"backend": jax.default_backend(),
                            "n_devices": len(jax.devices()),
                            "jax": jax.__version__},
                   "benchmarks": []}
    payload["benchmarks"] = [
        r for r in payload["benchmarks"]
        if not str(r.get("name", "")).startswith("device_scaling/")
    ] + RECORDS
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# merged {len(RECORDS)} device_scaling records into {path}")


if __name__ == "__main__":
    main()
