"""Chip smoke run: the FCT serving path on a TPU at TPC-H SF1 row counts.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # sharded path: 4-device mesh vs 1-device mesh

Generates the SF1 star schema from ``--seed`` (LINEITEM 6,000,000, PART
200,000, SUPPLIER 10,000, ORDERS 1,500,000 rows; ``text_len`` 8 and a 4096
term vocabulary are assumed, since TPC-H's text grammar is not tokenized),
plants three keyword sets at 8% of the rows of their relations, and answers
them through the entry points a user calls:

* one chip: ``fct_count`` kernel check, then every keyword set through
  ``FCTSession.query`` (cold, then twice warm), one set through a
  ``device_topk`` session, and every set through ``Gateway.submit`` over a
  ``SchemaRegistry`` holding the schema;
* ``--chips 4``: keyword sets 2 and 3 on a 4-device mesh (sharded store,
  MR1 ``all_to_all`` shuffle, reduce-scatter aggregation) and, as the
  comparison, on a 1-device mesh in the same process.  The star set (58
  CNs, 37 signature groups) is left to the one-chip run: on a v5e host each
  of its programs takes ~5 s to compile, which would double the call.  Its
  three-dim sharded program is compiled for a 2x2 v5e at SF1 shapes by
  ``tests/test_tpu_compile.py``.

Every answer must equal the host oracle (``fct_star`` / ``topk_terms``) bit
for bit; warm queries must compile nothing; the integer-exact Pallas kernel
must be the histogram path.  Progress lines go to stdout; the last line is
one JSON object ``{"ok": true, "device": {...}}``.  Any mismatch or
exception exits non-zero without that line, and so does a run where JAX
finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.compile_cache import setup_compile_cache  # noqa: E402

#: TPC-H SF1 row counts (TPC-H specification, clause 4.2.5)
SF1_ROWS = {"fact_rows": 6_000_000, "part_rows": 200_000,
            "supp_rows": 10_000, "order_rows": 1_500_000}
TEXT_LEN = 8
VOCAB = 4096
PLANT_FRAC = 0.08
#: keyword sets and the relations each keyword is planted in
KEYWORD_SETS = (
    {"PART": 4000, "SUPPLIER": 4001, "ORDERS": 4002},    # star, Q1-like
    {"LINEITEM": 4003, "ORDERS": 4004},
    {"PART": 4005, "LINEITEM": 4006, "SUPPLIER": 4007},
)
R_MAX = 4
TOP_K = 10
TENANT = "tpch_sf1"


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(phase: str, **fields) -> None:
    print(f"{phase}: {json.dumps(fields, default=str)}", flush=True)


def device_info() -> dict:
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def build_schema(seed: int):
    from repro.data.tpch import TpchConfig, generate, plant_keywords
    plant = {}
    for kws in KEYWORD_SETS:
        for rel, kw in kws.items():
            plant.setdefault(rel, []).append(kw)
    t0 = time.perf_counter()
    cfg = TpchConfig(text_len=TEXT_LEN, vocab_size=VOCAB, seed=seed,
                     **SF1_ROWS)
    schema = plant_keywords(generate(cfg), plant, frac=PLANT_FRAC,
                            seed=seed + 7)
    rows = {r.name: r.rows for r in [schema.fact, *schema.dims]}
    say("data", gen_s=round(time.perf_counter() - t0, 3), rows=rows,
        text_len=TEXT_LEN, vocab=VOCAB, seed=seed)
    return schema


def oracles(schema, keyword_sets):
    """Host answers per keyword set: (keywords, fct_star histogram)."""
    from repro.core.star import fct_star
    out = []
    for kws in keyword_sets:
        kw = tuple(kws.values())
        t0 = time.perf_counter()
        out.append((kw, fct_star(schema, kw, R_MAX)))
        say("oracle", keywords=kw, s=round(time.perf_counter() - t0, 3))
    return out


def check_answer(resp, kw, freq, label: str) -> None:
    from repro.core.star import topk_terms
    ids, counts = topk_terms(freq, kw, TOP_K)
    check(np.array_equal(resp.term_ids, ids)
          and np.array_equal(resp.freqs, counts),
          f"{label}: top-{TOP_K} differs from the oracle")
    if resp.all_freqs is not None:
        check(np.array_equal(resp.all_freqs, freq),
              f"{label}: histogram differs from fct_star")


def timed_query(session, req):
    t0 = time.perf_counter()
    resp = session.query(req)
    return resp, round((time.perf_counter() - t0) * 1e3, 3)


def run_queries(session, answers, label: str, warm_reps: int) -> dict:
    """Each keyword set cold, then ``warm_reps`` times warm; every answer is
    checked against the oracle and warm reps must compile nothing."""
    from repro.api import FCTRequest
    warm = {}
    for kw, freq in answers:
        req = FCTRequest(keywords=kw, top_k=TOP_K, r_max=R_MAX)
        resp, ms = timed_query(session, req)
        check_answer(resp, kw, freq, f"{label} cold {kw}")
        say(f"{label} cold", keywords=kw, wall_ms=ms,
            compiles=resp.engine_stats["traces"], n_cns=resp.n_cns,
            timings=resp.timings)
        for rep in range(warm_reps):
            resp, ms = timed_query(session, req)
            check_answer(resp, kw, freq, f"{label} warm {kw}")
            check(resp.engine_stats["traces"] == 0,
                  f"{label} warm {kw}: {resp.engine_stats['traces']} compiles")
            say(f"{label} warm", keywords=kw, rep=rep, wall_ms=ms,
                timings=resp.timings)
            warm.setdefault(str(kw), []).append(ms)
    return warm


def kernel_check(seed: int) -> None:
    """The integer-exact kernel against a numpy histogram, with totals past
    2^24 (float32-exactness) and past 2^31 (int32 wrap-around)."""
    from repro.data.schema import tokens_histogram
    from repro.kernels.fct_count.kernel import fct_count_pallas_exact
    rng = np.random.default_rng(seed)
    n = 1 << 16
    tokens = rng.integers(0, VOCAB, (n, TEXT_LEN)).astype(np.int32)
    weights = rng.integers(0, 1 << 28, (n,)).astype(np.int32)
    want = tokens_histogram(tokens, weights.astype(np.int64), VOCAB)
    want = ((want + (1 << 31)) % (1 << 32) - (1 << 31)).astype(np.int32)
    args = (jnp.asarray(tokens.T), jnp.asarray(weights))
    compiled = fct_count_pallas_exact.lower(*args, VOCAB).compile()
    check("tpu_custom_call" in compiled.as_text(),
          "fct_count_pallas_exact did not compile to a Mosaic kernel")
    got = np.asarray(compiled(*args))
    check(np.array_equal(got, want), "fct_count_pallas_exact != numpy")
    say("kernel", rows=n, text_len=TEXT_LEN, vocab=VOCAB,
        max_total=int(np.abs(want).max()), wraps=bool((want < 0).any()))


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def one_chip(schema, answers) -> None:
    from repro.api import FCTRequest, FCTSession, SessionConfig
    from repro.kernels.fct_count import ops
    from repro.launch.mesh import make_worker_mesh
    from repro.serve import Gateway, SchemaRegistry

    mesh = make_worker_mesh(1)
    registry = SchemaRegistry(mesh=mesh)
    registry.register(TENANT, schema)
    session = registry.session(TENANT)
    check(session.config.histogram_backend == "auto",
          "smoke must run the default histogram backend")
    ops.reset_path_counts()
    run_queries(session, answers, "session", warm_reps=2)
    check(ops.PATH_COUNTS["pallas_exact"] > 0 and ops.PATH_COUNTS["ref"] == 0,
          f"histogram paths {ops.PATH_COUNTS}: want pallas_exact only")
    say("paths", **ops.PATH_COUNTS)

    # one keyword set through the O(k)-transfer device top-k finalize
    kw, freq = answers[1]
    topk_session = FCTSession(schema, mesh=mesh,
                              config=SessionConfig(device_topk=True))
    req = FCTRequest(keywords=kw, top_k=TOP_K, r_max=R_MAX)
    for rep in ("cold", "warm"):
        resp, ms = timed_query(topk_session, req)
        check(resp.finalize == "device_topk", "device top-k path not taken")
        check_answer(resp, kw, freq, f"device_topk {rep} {kw}")
        if rep == "warm":
            check(resp.engine_stats["traces"] == 0, "device_topk warm compiled")
        say(f"device_topk {rep}", keywords=kw, wall_ms=ms,
            compiles=resp.engine_stats["traces"],
            d2h_bytes=resp.engine_stats["device_to_host_bytes"])

    # the serving front door over the same tenant session; one request at a
    # time, so each rides the summed program family the session compiled
    with Gateway(registry) as gateway:
        for kw, freq in answers:
            req = FCTRequest(keywords=kw, top_k=TOP_K, r_max=R_MAX)
            for rep in ("miss", "hit"):
                t0 = time.perf_counter()
                resp = gateway.submit(TENANT, req).result(timeout=600)
                ms = round((time.perf_counter() - t0) * 1e3, 3)
                check_answer(resp, kw, freq, f"gateway {rep} {kw}")
                check(resp.engine_stats.get("traces", 0) == 0,
                      "gateway request compiled")
                say(f"gateway {rep}", keywords=kw, wall_ms=ms,
                    cache_hit=resp.cache_hit)
    check(ops.PATH_COUNTS["ref"] == 0, "a ref histogram was traced")
    store = registry.store_bytes() + topk_session.store.resident_bytes
    say("memory", store_resident_bytes=store, peak_bytes_in_use=peak_bytes())
    topk_session.close()
    registry.close()


def four_chips(schema, answers) -> None:
    from repro.api import FCTSession
    from repro.launch.mesh import make_worker_mesh

    warm = {}
    for n in (4, 1):
        session = FCTSession(schema, mesh=make_worker_mesh(n))
        warm[n] = run_queries(session, answers, f"mesh{n}", warm_reps=1)
        say(f"mesh{n} store", store_resident_bytes=session.store.resident_bytes,
            peak_bytes_in_use=peak_bytes())
        session.close()
    say("warm_ms", mesh4=warm[4], mesh1=warm[1])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    info = device_info()
    if info["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {info['platform']!r})",
              file=sys.stderr)
        return 2
    say("device", **info, jax=jax.__version__,
        compile_cache=setup_compile_cache())
    if args.chips == 4:
        check(info["count"] >= 4, f"--chips 4 needs 4 devices, "
              f"have {info['count']}")
    else:
        kernel_check(args.seed)
    schema = build_schema(args.seed)
    answers = oracles(schema, KEYWORD_SETS[1:] if args.chips == 4
                      else KEYWORD_SETS)
    if args.chips == 4:
        four_chips(schema, answers)
    else:
        one_chip(schema, answers)
    info["count"] = args.chips
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
