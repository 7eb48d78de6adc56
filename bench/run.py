"""Benchmark entry: one run of one cell of ``BENCHMARK.json``.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run generates the cell's warehouse from the seed, serves it through
``Gateway.submit`` on one ``SchemaRegistry`` tenant, warms up every program
the cell's traffic uses (set-up), drives a closed loop of clients for
``--seconds``, waits for every request sent inside the window, checks each
answer against ``bench/reference`` and prints one JSON line as the last line
of standard output.  With ``--trace 0`` the line carries the cell's
end-to-end metrics; with ``--trace 1`` the window runs under the JAX
profiler and the line carries the per-layer metrics, read by the readers in
``bench/metrics/``.  A run on a machine where JAX finds no TPU, or fewer
chips than the cell asks for, exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import trace_reduce  # noqa: E402
from bench.loops import Record  # noqa: E402
from bench.reference.data import load_config  # noqa: E402

TENANT = "warehouse"
#: how long past the window's close a request may take to answer
GRACE_S = 60.0
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_out" / "trace"
#: the numbers ``correct`` is decided on, each with its limit (exact: 0)
LIMITS = {"unanswered": 0, "hist_mismatch": 0, "topk_mismatch": 0,
          "max_count_gap": 0}


def say(what: str, **fields) -> None:
    print(f"{what}: {json.dumps(fields, default=str)}", file=sys.stderr,
          flush=True)


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_mix(name: str) -> dict:
    return json.loads((ROOT / "bench" / "mixes" / f"{name}.json").read_text())


def module(kind: str, name: str):
    """``bench/<kind>/<name>.py``: a schema, generator, loop or metric."""
    return importlib.import_module(f"bench.{kind}.{name}")


# -- program plumbing ---------------------------------------------------------

class CompileEvents:
    """Counts XLA compiles and persistent-cache hits from JAX's monitoring
    events (process-wide; read as deltas)."""

    def __init__(self) -> None:
        import jax
        self.compiles = self.hits = self.misses = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def snapshot(self) -> dict:
        return {"xla_compiles": self.compiles, "cache_hits": self.hits,
                "cache_misses": self.misses,
                "compile_s": round(self.compile_s, 3)}


def host_spans(records: List[Record]) -> list:
    """(name, start_ns, end_ns) of each request's client wait and of the
    program's spans on its trace, on the perf_counter clock."""
    out = []
    for r in records:
        out.append(("client.wait", int(r.sent * 1e9), int(r.done * 1e9)))
        trace = getattr(r.response, "trace", None)
        for s in (trace.spans() if trace is not None else ()):
            out.append((s.name, s.t0_ns, s.t0_ns + s.dur_ns))
    return out


# -- arithmetic of the end-to-end metrics -------------------------------------

def end_to_end(records: List[Record], t_open: float) -> dict:
    """qps over all the work and all the time: requests completed / (last
    completion - window open); latencies from submit to answer, their
    percentiles linearly interpolated (numpy's default)."""
    done = [r for r in records if r.done is not None]
    if not done:
        return {}
    lat = [(r.done - r.sent) * 1e3 for r in done]
    span = max(r.done for r in done) - t_open
    return {"qps": len(done) / span,
            "latency_p50_ms": float(np.percentile(lat, 50)),
            "latency_p95_ms": float(np.percentile(lat, 95))}


# -- correctness --------------------------------------------------------------

def check_answers(records: List[Record], ref, mix: dict,
                  round_to: Optional[str] = None) -> dict:
    """Every answer against the reference; ``round_to`` swaps in the control
    (the reference held in a narrower type) for the program's answers."""
    out = {k: 0 for k in LIMITS}
    answers: Dict[tuple, tuple] = {}
    control: Dict[tuple, tuple] = {}
    for r in records:
        if r.done is None:
            out["unanswered"] += 1
            continue
        if r.keywords not in answers:
            answers[r.keywords] = ref.answer(r.keywords, mix["r_max"],
                                             mix["top_k"])
        want_h, want_ids, want_f = answers[r.keywords]
        if round_to is None:
            got_h = np.asarray(r.response.all_freqs, np.int64)
            got_ids = np.asarray(r.response.term_ids)
            got_f = np.asarray(r.response.freqs, np.int64)
        else:
            if r.keywords not in control:
                control[r.keywords] = ref.answer(r.keywords, mix["r_max"],
                                                 mix["top_k"], round_to)
            got_h, got_ids, got_f = control[r.keywords]
        gap = int(np.abs(got_h - want_h).max())
        out["max_count_gap"] = max(out["max_count_gap"], gap)
        out["hist_mismatch"] += int(gap > 0)
        out["topk_mismatch"] += int(not (np.array_equal(got_ids, want_ids)
                                         and np.array_equal(got_f, want_f)))
    out["checked"] = sum(r.done is not None for r in records)
    return out


def plan_work(records: List[Record], ref, mix: dict) -> dict:
    """The histogram work the completed requests need, from the reference's
    tuple sets (``Reference.cn_work``), summed over the requests."""
    per_set: Dict[tuple, dict] = {}
    out = {"rows": 0, "histograms": 0, "bytes": 0}
    for r in records:
        if r.done is None:
            continue
        if r.keywords not in per_set:
            per_set[r.keywords] = ref.cn_work(r.keywords, mix["r_max"])
        w = per_set[r.keywords]
        out["rows"] += w["rows"]
        out["histograms"] += w["relations"]
        out["bytes"] += w["bytes"]
    return out


# -- one run ------------------------------------------------------------------

def run_cell(cfg: dict, mix: dict, *, chips: int, seed: int, seconds: float,
             trace: bool, metrics: List[dict], t_process: float,
             trace_dir: Path = TRACE_DIR, keep_trace: bool = False,
             round_to: Optional[str] = None, sound_too: bool = False) -> dict:
    """Set-up, window, checks; returns the result line's object.

    ``round_to`` compares the control's answers in place of the program's;
    with ``sound_too`` the program's own checks are returned beside them
    (``program_checks``, ``control_checks``) for ``bench/control.py``."""
    import jax

    from repro.api import FCTRequest
    from repro.launch.mesh import make_worker_mesh
    from repro.serve import Gateway, GatewayConfig, SchemaRegistry

    events = CompileEvents()
    schema = module("schemas", cfg["schema"])
    t0 = time.perf_counter()
    wh = schema.generate(cfg, seed)
    gen_s = time.perf_counter() - t0
    traffic = module("generators", mix["generator"]).make(
        mix, wh, np.random.default_rng([seed, 1]))
    registry = SchemaRegistry(mesh=make_worker_mesh(chips))
    registry.register(TENANT, schema.to_program(wh))
    gateway = Gateway(registry, GatewayConfig(**mix["gateway"]))
    session = registry.session(TENANT)

    def submit(kws):
        return gateway.submit(TENANT, FCTRequest(
            keywords=kws, top_k=mix["top_k"], r_max=mix["r_max"]))

    # warm-up through the same submit the clients use, each round's sets
    # sent together: every program the window runs compiles here
    t1 = time.perf_counter()
    plan_ms = 0.0
    for round_ in traffic.warmup():
        for resp in [f.result() for f in [submit(k) for k in round_]]:
            plan_ms += resp.timings["plan_ms"]
    warm_s = time.perf_counter() - t1
    setup = {"generate_s": round(gen_s, 3), "warmup_s": round(warm_s, 3),
             "warmup_plan_s": round(plan_ms / 1e3, 3),
             "store_upload_bytes": session.store.stats().get(
                 "store_upload_bytes", 0),
             "programs": session.engine.stats()["traces"],
             **events.snapshot()}

    before = dict(events.snapshot(), traces=session.engine.stats()["traces"])
    prof = None
    if trace:
        prof = trace_reduce.Profile(trace_dir)
        prof.start()
    t_open = time.perf_counter()
    setup_s = t_open - t_process
    say("traffic", **traffic.describe())
    say("setup", setup_s=round(setup_s, 3), **setup)
    if prof is not None:
        prof.mark_open()
    records = module("loops", mix["loop"]).drive(submit, traffic, t_open,
                                                  seconds, GRACE_S)
    t_end = max([r.done for r in records if r.done is not None],
                default=time.perf_counter())
    if prof is not None:
        prof.stop()
    after = dict(events.snapshot(), traces=session.engine.stats()["traces"])
    in_window = {"programs_traced": after["traces"] - before["traces"],
                 "xla_compiles": after["xla_compiles"]
                 - before["xla_compiles"]}
    stats = jax.devices()[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    store_bytes = registry.store_bytes()
    gw = gateway.stats()[TENANT]
    gateway.close()
    registry.close()
    del gateway, registry, session

    done = [r for r in records if r.done is not None]
    lat = [(r.done - r.sent) * 1e3 for r in done]
    say("window", requests=len(records), completed=len(done),
        window_s=round(t_end - t_open, 3), **in_window,
        windows_flushed=gw.get("windows_flushed"),
        max_window_queries=gw.get("max_window_queries"),
        result_cache_hits=gw.get("result_hits"),
        plan_hits=gw.get("plan_hits"), plan_misses=gw.get("plan_misses"),
        latency_samples=len(lat),
        latency_max_ms=round(max(lat), 3) if lat else None)
    say("memory", peak_bytes_in_use=peak, store_resident_bytes=store_bytes)
    for r in records:
        if r.error is not None:
            say("request_failed", client=r.client, keywords=r.keywords,
                error=r.error)

    ref = schema.Reference(wh)
    t2 = time.perf_counter()
    checks = check_answers(records, ref, mix, round_to=round_to)
    sound = check_answers(records, ref, mix) if sound_too else None
    say("reference", s=round(time.perf_counter() - t2, 3),
        keyword_sets=len({r.keywords for r in done}),
        checked=checks.pop("checked"))

    if trace:
        ctx = {"records": done,
               "profile": prof.reduce(t_open, t_end, host_spans(done)),
               "work": plan_work(done, ref, mix),
               "device_kind": jax.devices()[0].device_kind}
        if keep_trace:     # what a test needs to reduce the trace again
            (trace_dir / "window.json").write_text(json.dumps({
                "marker_ns": prof.marker_ns, "open_ns": int(t_open * 1e9),
                "end_ns": int(t_end * 1e9), "spans": host_spans(done),
                "reduced": ctx["profile"]}))
        else:
            shutil.rmtree(trace_dir, ignore_errors=True)
        values = {}
        for m in metrics:
            v = module("metrics", m["name"]).read(ctx)
            if v is not None:
                values[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = end_to_end(records, t_open)
        e2e["setup_s"] = setup_s
        values = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                  for m in metrics if m["name"] in e2e}

    correct = bool(done) and all(checks[k] <= LIMITS[k] for k in LIMITS)
    dev = jax.devices()[0]
    result = {"correct": correct, "attempted": len(records),
              "failed": len(records) - len(done), "metrics": values,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": chips, "memory_peak_bytes": peak}}
    if trace and ctx["profile"] is not None:
        result["device"]["busy_s"] = ctx["profile"]["busy_s"]
        result["device"]["window_s"] = ctx["profile"]["window_s"]
        result["breakdown"] = ctx["profile"]["breakdown"]
    if sound is not None:
        sound.pop("checked")
        result["program_checks"], result["control_checks"] = sound, checks
    result["checks"] = {k: {"value": checks[k], "limit": LIMITS[k]}
                        for k in LIMITS}
    for k in LIMITS:
        print(f"check {k}: {checks[k]} (limit {LIMITS[k]})", file=sys.stderr,
              flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="keep the profiler trace in this directory")
    args = ap.parse_args(argv)

    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}", file=sys.stderr)
        return 2
    cell = cells[args.workload]

    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    key = "per_layer" if args.trace else "end_to_end"
    metrics = [m for m in bench[key]
               if args.workload in m.get("workloads", [args.workload])]
    trace_dir = Path(args.keep_trace) if args.keep_trace else TRACE_DIR
    result = run_cell(load_config(cell["config"]), load_mix(cell["traffic"]),
                      chips=cell["chips"], seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      metrics=metrics, t_process=T_PROCESS,
                      trace_dir=trace_dir, keep_trace=bool(args.keep_trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
