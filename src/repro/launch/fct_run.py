"""FCT query launcher: generate (or load) a star database and answer an FCT
query through the session service API.

    python -m repro.launch.fct_run --keywords alps bordeaux --top-k 8 \
        --mode skew --rho 4 --scale 2 --skew 1.0 --repeat 3

Queries execute through an FCTSession over the runtime engine: ``--repeat``
re-runs the query to show the warm-cache latency next to the cold one.  The
cold/warm label comes from the engine's actual trace delta for that rep, not
the rep index — with a shared process-wide cache, rep 0 can already be warm.
"""
from __future__ import annotations

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--keywords", nargs="+", default=["alps", "bordeaux"])
    ap.add_argument("--top-k", type=int, default=8)
    ap.add_argument("--r-max", type=int, default=4)
    ap.add_argument("--mode", default="uniform",
                    choices=["uniform", "skew", "round_robin"])
    ap.add_argument("--rho", type=int, default=4)
    ap.add_argument("--sample-frac", type=float, default=0.25)
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--skew", type=float, default=0.0)
    ap.add_argument("--repeat", type=int, default=1,
                    help="run the query N times (warm runs hit the "
                         "compiled-executable cache)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write every rep's span tree as Chrome trace-event "
                         "JSON (chrome://tracing / Perfetto)")
    args = ap.parse_args()

    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    from examples.quickstart import TOK, build_db
    from repro.api import FCTRequest, FCTSession
    from repro.obs import write_chrome_trace

    schema = build_db(n_fact=int(2000 * args.scale))
    session = FCTSession(schema, tokenizer=TOK)  # process-wide engine
    req = FCTRequest(keywords=tuple(args.keywords), top_k=args.top_k,
                     r_max=args.r_max, mode=args.mode, rho=args.rho,
                     sample_frac=args.sample_frac)
    res, traces = None, []
    for rep in range(max(1, args.repeat)):
        t0 = time.perf_counter()
        res = session.query(req)
        ms = (time.perf_counter() - t0) * 1e3
        traces.append(res.trace)
        label = "cold" if res.cold else "warm"  # from the engine trace delta
        t = res.timings
        print(f"run {rep} ({label}): {ms:.1f}ms "
              f"(plan {t['plan_ms']:.1f} dispatch {t['dispatch_ms']:.1f} "
              f"collect {t['collect_ms']:.1f} "
              f"finalize {t['finalize_ms']:.1f}) "
              f"traces={res.engine_stats['traces']}")
    print(f"query={args.keywords} mode={args.mode} "
          f"CNs={res.n_cns} (joined {res.n_joined_cns}) "
          f"shuffle={res.shuffle_bytes / 1e6:.2f}MB "
          f"imbalance={res.imbalance:.2f}")
    st = session.stats()
    print(f"engine: {st['entries']} cached executables, "
          f"{st['hits']} hits / {st['misses']} misses, "
          f"{st['traces']} traces, {st['evictions']} evictions, "
          f"{st['batches_run']} batched dispatches for {st['cns_run']} CNs; "
          f"plan cache {st['plan_hits']} hits")
    for word, freq in res.topk():
        print(f"  {word:16s} {freq}")
    if args.trace_out:
        n_events = write_chrome_trace(args.trace_out, traces)
        print(f"trace -> {args.trace_out} ({len(traces)} reps, "
              f"{n_events} events)")


if __name__ == "__main__":
    main()
