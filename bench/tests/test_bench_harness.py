"""The harness on the CPU at a small size: traffic, metric arithmetic, the
result line, the refusal without a TPU, and ``correct`` under the control
and under faults planted in the timed path."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run  # noqa: E402
from bench.generators import keyword_sets  # noqa: E402
from bench.loops import Record, closed  # noqa: E402
from bench.reference.data import generate, load_config  # noqa: E402

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
                 "checks"]


def small_config(fact=60000):
    """Fewer rows and a smaller text pool; the TPC-H widths and grammar as
    configured.  Counts here pass 256, so a bfloat16 histogram cannot
    hold them."""
    cfg = load_config("tpch-sf1")
    cfg["fact"]["rows"] = fact
    for d, n in zip(cfg["dims"], (400, 100, 800)):
        d["rows"] = n
    cfg["text_pool_chars"] = 2_000_000
    cfg["dims"][1]["customer_remarks"] = 2
    return cfg


@pytest.fixture(scope="module")
def small_wh():
    return generate(small_config(), 2**31 + 21)


def take(it, n):
    return [next(it) for _ in range(n)]


@pytest.mark.parametrize("mix", ["star_repeat"])
def test_mix_is_deterministic_per_seed(mix, small_wh):
    """The words are the mix's: every seed asks for the same sets, in the
    same order, over its own data."""
    m = run.load_mix(mix)
    gen = run.module("generators", m["generator"])
    a = gen.make(m, small_wh, np.random.default_rng([2**31 + 3, 1]))
    b = gen.make(m, small_wh, np.random.default_rng([2**31 + 3, 1]))
    other = generate(small_config(), 2**31 + 22)
    c = gen.make(m, other, np.random.default_rng([2**31 + 4, 1]))
    assert a.warmup() == b.warmup() == c.warmup()
    for i in range(a.clients):
        assert take(a.client(i), 20) == take(b.client(i), 20)
    assert [[small_wh.terms[k] for k in s] for s in a.sets] == [
        s["words"] for s in m["sets"]]
    assert not np.array_equal(small_wh.fact_text, other.fact_text)


def test_repeat_clients_never_share_a_set(small_wh):
    m = run.load_mix("star_repeat")
    t = keyword_sets.make(m, small_wh, np.random.default_rng(7))
    c0, c1 = set(take(t.client(0), 10)), set(take(t.client(1), 10))
    assert [len(c0), len(c1)] == [len(c) for c in m["client_sets"]]
    assert not c0 & c1
    assert c0 | c1 == set(t.sets)
    # warm-up: every set alone, then every pair the two clients can have
    # in flight together, sent as one round
    rounds = t.warmup()
    assert [r for r in rounds if len(r) == 1] == [[s] for s in t.sets]
    assert sorted(tuple(r) for r in rounds if len(r) == 2) == sorted(
        (x, y) for x in c0 for y in c1)


def test_sets_draw_their_words_from_their_bands(small_wh):
    m = run.load_mix("star_repeat")
    share = keyword_sets.term_shares(small_wh.fact_text, small_wh.vocab)
    t = keyword_sets.make(m, small_wh, np.random.default_rng(1))
    bands = set()
    for kws, s in zip(t.sets, m["sets"]):
        assert len(set(kws)) == len(kws)
        for kw, band in zip(kws, s["bands"]):
            lo, hi = m["bands"][band]
            assert lo <= share[kw] < hi
            bands.add(band)
    assert bands == {"rare", "mid", "common"}     # rare to common
    wrong = json.loads(json.dumps(m))
    wrong["sets"][0]["bands"] = ["common", "rare"]
    with pytest.raises(ValueError, match="outside band"):
        keyword_sets.make(wrong, small_wh, np.random.default_rng(1))


def test_closed_loop_starts_clients_together():
    from concurrent.futures import Future

    class Two:
        clients = 2

        def client(self, i):
            return iter([(i,)] * 1000)

    def submit(kws):
        f = Future()
        time.sleep(0.05)
        f.set_result(kws)
        return f

    t_open = time.perf_counter()
    recs = closed.drive(submit, Two(), t_open, 0.3, 5.0)
    first = [min(r.sent for r in recs if r.client == i) for i in (0, 1)]
    assert abs(first[0] - first[1]) < 0.03
    assert all(r.sent < t_open + 0.3 for r in recs)
    assert all(r.done is not None for r in recs)


def test_end_to_end_arithmetic():
    recs = [Record(0, (1,), sent=s, done=d) for s, d in
            [(0.0, 1.0), (1.0, 2.5), (0.5, 2.0), (2.0, 4.0)]]
    recs.append(Record(1, (2,), sent=3.5))          # never answered
    e2e = run.end_to_end(recs, t_open=0.0)
    assert e2e["qps"] == pytest.approx(4 / 4.0)         # 4 done by t=4
    lat = [1000.0, 1500.0, 1500.0, 2000.0]
    assert e2e["latency_p50_ms"] == pytest.approx(1500.0)
    # linear interpolation: rank 0.95 * 3 = 2.85 -> 1500 + 0.85 * 500
    assert e2e["latency_p95_ms"] == pytest.approx(1925.0)
    assert e2e["latency_p95_ms"] == pytest.approx(np.percentile(lat, 95))


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "sf1-star-repeat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def run_small(mix="star_repeat", trace=False, **kw):
    bench = run.load_benchmark()
    metrics = bench["per_layer" if trace else "end_to_end"]
    return run.run_cell(small_config(), run.load_mix(mix), chips=1,
                        seed=2**31 + 21, seconds=1.5, trace=trace,
                        metrics=metrics, t_process=time.perf_counter(), **kw)


@pytest.fixture
def fresh_engine(monkeypatch):
    """Compile the timed path anew, so a planted fault is traced into it."""
    from repro.runtime import engine
    from repro.runtime.cache import ExecutableCache
    monkeypatch.setattr(engine, "_DEFAULT_ENGINE",
                        engine.FCTEngine(cache=ExecutableCache()))
    return engine


def test_sound_run_line_and_checks(fresh_engine):
    res = run_small()
    assert list(res) == CONTRACT_KEYS
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"qps", "latency_p50_ms", "latency_p95_ms",
                                   "setup_s"}
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        res["device"])
    assert all(c["value"] == 0 for c in res["checks"].values())
    json.dumps(res)


def test_control_is_not_correct(fresh_engine):
    res = run_small(round_to="bfloat16")
    assert res["correct"] is False
    assert res["checks"]["max_count_gap"]["value"] > 0


def test_answer_altered_where_produced_is_not_correct(fresh_engine,
                                                      monkeypatch):
    collect = fresh_engine.FCTEngine._collect

    def altered(self, lazy):
        out = collect(self, lazy)
        out[..., 7] += 1
        return out

    monkeypatch.setattr(fresh_engine.FCTEngine, "_collect", altered)
    res = run_small()
    assert res["correct"] is False
    assert res["checks"]["hist_mismatch"]["value"] == res["attempted"]


def test_half_the_rows_left_out_is_not_correct(fresh_engine, monkeypatch):
    import jax.numpy as jnp

    from repro.core import fct
    hist = fct.weighted_histogram

    def half(tokens, weights, vocab, backend="auto"):
        keep = (jnp.arange(weights.shape[-1]) % 2 == 0).astype(weights.dtype)
        return hist(tokens, weights * keep * 2, vocab, backend=backend)

    monkeypatch.setattr(fct, "weighted_histogram", half)
    res = run_small()
    assert res["correct"] is False
    assert res["checks"]["hist_mismatch"]["value"] > 0


def test_traced_run_reports_program_spans(fresh_engine):
    res = run_small(trace=True,
                    trace_dir=Path(os.environ.get("TMPDIR", "/tmp"))
                    / f"bench-trace-{os.getpid()}")
    assert res["correct"] is True
    # the CPU has no device plane: only the program-span readers report
    assert {"plan_ms", "dispatch_ms", "gateway_wait_ms"} <= set(
        res["metrics"])
    assert res["metrics"]["plan_ms"]["value"] > 0
