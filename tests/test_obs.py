"""Observability subsystem (repro/obs): registry thread-safety, histogram
bucket math, snapshot aggregation and label isolation, span nesting across
the sync and pipelined session paths, Chrome trace export, and the
JSON-lines reporter."""
import json
import threading

import pytest

from repro.api import FCTRequest, FCTSession, SessionConfig
from repro.obs import (
    JsonLinesReporter,
    MetricsRegistry,
    Trace,
    annotate,
    chrome_trace,
    current_trace,
    render_key,
    set_annotator,
    span,
    write_chrome_trace,
)

from test_engine import _crafted_schema


# -- metrics: instruments and registry ----------------------------------------

def test_counter_gauge_basics():
    m = MetricsRegistry()
    c = m.counter("x.count")
    c.inc()
    c.inc(4)
    assert c.value == 5
    c.reset()
    assert c.value == 0
    g = m.gauge("x.depth")
    assert g.add(3) == 3
    assert g.add(-1) == 2
    g.set_max(7)
    g.set_max(5)                          # lower: no effect
    assert g.value == 7
    g.set(1)
    assert g.value == 1


def test_registry_thread_safety_under_concurrent_bumps():
    m = MetricsRegistry()
    c = m.counter("c")
    g = m.gauge("g")
    h = m.histogram("h", buckets=(1.0, 10.0, 100.0))
    n_threads, n_iter = 8, 2000

    def worker():
        for i in range(n_iter):
            c.inc()
            g.add(1)
            g.add(-1)
            h.observe(float(i % 50))

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * n_iter
    assert g.value == 0
    assert h.count == n_threads * n_iter
    snap = m.snapshot()
    assert snap["counters"]["c"] == n_threads * n_iter
    assert snap["histograms"]["h"]["count"] == n_threads * n_iter


def test_histogram_bucket_math_le_semantics():
    m = MetricsRegistry()
    h = m.histogram("lat", buckets=(1.0, 2.0, 4.0, 8.0))
    for v in (0.5, 1.0, 1.5, 3.0, 8.0, 100.0):
        h.observe(v)
    snap = m.snapshot()["histograms"]["lat"]
    # Prometheus le semantics: bucket i counts values <= bounds[i];
    # 1.0 lands in the le=1 bucket, 8.0 in le=8, 100.0 overflows to +inf
    assert snap["buckets"] == {"1.0": 2, "2.0": 1, "4.0": 1, "8.0": 1,
                               "+inf": 1}
    assert snap["count"] == 6
    assert snap["sum"] == pytest.approx(114.0)
    assert 0.0 < snap["p50"] <= 2.0
    assert snap["p50"] <= snap["p95"] <= snap["p99"]
    # percentiles interpolate within the bucket, never above its bound
    assert h.percentile(10.0) <= 1.0


def test_histogram_rejects_empty_buckets():
    with pytest.raises(ValueError):
        MetricsRegistry().histogram("h", buckets=())
    with pytest.raises(ValueError):
        MetricsRegistry().gauge("g", agg="median")


def test_snapshot_aggregates_same_key_instruments():
    # per-component instruments with the same (name, labels) merge:
    # counters/sum-gauges add, max-gauges take the max, histograms pool
    m = MetricsRegistry()
    m.counter("c").inc(2)
    m.counter("c").inc(3)
    m.gauge("depth").add(1)
    m.gauge("depth").add(2)
    m.gauge("peak", agg="max").set(5)
    m.gauge("peak", agg="max").set(9)
    m.histogram("h", buckets=(1.0, 2.0)).observe(0.5)
    m.histogram("h", buckets=(1.0, 2.0)).observe(1.5)
    snap = m.snapshot()
    assert snap["counters"]["c"] == 5
    assert snap["gauges"]["depth"] == 3
    assert snap["gauges"]["peak"] == 9
    assert snap["histograms"]["h"]["count"] == 2


def test_labeled_registry_isolates_tenants():
    m = MetricsRegistry()
    a = m.labeled(schema="a")
    b = m.labeled(schema="b")
    a.counter("q.served").inc(7)
    b.counter("q.served").inc(2)
    a.histogram("lat_ms", buckets=(1.0, 10.0)).observe(0.5)
    snap = m.snapshot()
    assert snap["counters"]["q.served{schema=a}"] == 7
    assert snap["counters"]["q.served{schema=b}"] == 2
    assert "lat_ms{schema=a}" in snap["histograms"]
    # filtered snapshot: only tenant a's instruments
    only_a = m.snapshot(labels={"schema": "a"})
    assert "q.served{schema=b}" not in only_a["counters"]
    assert only_a["counters"]["q.served{schema=a}"] == 7
    # nested labels merge, call-site labels win over facade labels
    assert render_key("n", {"b": 1, "a": 2}) == "n{a=2,b=1}"
    inner = a.labeled(stage="plan")
    inner.counter("n").inc()
    assert m.snapshot()["counters"]["n{schema=a,stage=plan}"] == 1


def test_gauge_fn_evaluated_outside_lock():
    m = MetricsRegistry()

    def resident():
        # taking the registry lock here would deadlock if snapshot held it
        with m._lock:
            return 42

    m.gauge_fn("resident_bytes", resident, schema="a")
    assert m.snapshot()["gauges"]["resident_bytes{schema=a}"] == 42


def test_values_reads_many_instruments_in_one_cut():
    m = MetricsRegistry()
    c1, c2 = m.counter("a"), m.counter("b")
    c1.inc(3)
    c2.inc(4)
    assert m.values(c1, c2) == [3, 4]


# -- tracing ------------------------------------------------------------------

def test_span_nesting_and_ordering():
    tr = Trace(request_id="q1")
    with tr.activate():
        assert current_trace() is tr
        with span("plan", n=2) as outer:
            with span("inner"):
                pass
        with span("dispatch"):
            pass
    assert current_trace() is None
    spans = tr.spans()
    names = [s.name for s in spans]
    assert names == ["plan", "inner", "dispatch"]
    by_name = {s.name: s for s in spans}
    assert by_name["inner"].parent_id == by_name["plan"].span_id
    assert by_name["plan"].parent_id == 0
    assert by_name["dispatch"].parent_id == 0
    assert outer.args == {"n": 2}
    assert by_name["plan"].dur_ns >= by_name["inner"].dur_ns


def test_span_without_active_trace_is_noop():
    with span("orphan") as s:
        s.args["x"] = 1                  # scratch span: writable, unrecorded
    assert current_trace() is None


class _Recorder:
    """Span annotator that records the names it opens and closes."""

    def __init__(self):
        self.events = []

    def __call__(self, name):
        rec = self

        class _Ctx:
            def __enter__(self):
                rec.events.append(("open", name))

            def __exit__(self, *exc):
                rec.events.append(("close", name))

        return _Ctx()

    def opened(self):
        return [n for kind, n in self.events if kind == "open"]


@pytest.fixture
def recorder():
    rec = _Recorder()
    prev = set_annotator(rec)
    yield rec
    set_annotator(prev)


def test_runtime_installs_the_profiler_annotator():
    import jax

    import repro.runtime  # noqa: F401  (installs on import)
    prev = set_annotator(None)
    set_annotator(prev)
    assert prev is jax.profiler.TraceAnnotation


def test_span_opens_the_annotator_under_its_name(recorder):
    tr = Trace()
    with tr.activate():
        with span("engine.dispatch_group"):
            with span("engine.enqueue"):
                pass
    assert recorder.events == [
        ("open", "engine.dispatch_group"), ("open", "engine.enqueue"),
        ("close", "engine.enqueue"), ("close", "engine.dispatch_group")]
    with annotate("dispatch"):
        pass
    assert recorder.opened()[-1] == "dispatch"


def test_span_without_active_trace_calls_no_annotator(recorder):
    with span("orphan"):
        pass
    assert current_trace() is None
    assert recorder.events == []


def test_add_span_records_from_foreign_threads():
    tr = Trace()
    results = []
    barrier = threading.Barrier(4, timeout=60)  # overlap: distinct OS tids

    def worker(i):
        barrier.wait()
        tr.add_span("stage", 1000 * i, 10, idx=i)
        results.append(i)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = tr.spans()
    assert len(spans) == 4 == len(results)
    assert [s.args["idx"] for s in spans] == [0, 1, 2, 3]  # t0_ns order
    assert len({s.thread_id for s in spans}) == 4


def test_chrome_trace_is_valid_json_with_events():
    tr = Trace(request_id="q42")
    with tr.activate():
        with span("plan"):
            with span("inner"):
                pass
    doc = chrome_trace([tr, None])       # None entries are skipped
    text = json.dumps(doc)
    parsed = json.loads(text)
    events = parsed["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"plan", "inner"}
    for e in xs:
        assert {"pid", "tid", "ts", "dur"} <= set(e)
    assert any(e["ph"] == "M" for e in events)  # process_name metadata


def test_write_chrome_trace(tmp_path):
    tr = Trace()
    with tr.activate():
        with span("plan"):
            pass
    out = tmp_path / "trace.json"
    n = write_chrome_trace(str(out), [tr])
    assert n >= 1
    assert json.loads(out.read_text())["traceEvents"]


# -- session integration: sync vs pipelined span trees ------------------------

TIMING_KEYS = {"plan_ms", "dispatch_ms", "collect_ms", "finalize_ms",
               "execute_ms", "total_ms"}


def test_sync_and_pipelined_paths_share_span_and_timing_shape():
    schema, kws = _crafted_schema(seed=0)
    session = FCTSession(schema, metrics=MetricsRegistry())
    req = FCTRequest(keywords=tuple(kws), r_max=3)
    sync_resp = session.query(req)
    assert set(sync_resp.timings) == TIMING_KEYS
    stage_names = {"plan", "dispatch", "collect", "finalize"}
    sync_names = set(sync_resp.trace.span_names())
    assert stage_names <= sync_names

    futs = [session.submit(FCTRequest(keywords=tuple(kws), r_max=3, salt=s))
            for s in (1, 2, 3)]
    for fut in futs:
        resp = fut.result(timeout=300)
        assert set(resp.timings) == TIMING_KEYS
        names = set(resp.trace.span_names())
        assert stage_names <= names, names
        # stage spans are ordered: plan ends before dispatch starts
        spans = {s.name: s for s in resp.trace.spans()
                 if s.name in stage_names}
        assert spans["plan"].t0_ns <= spans["dispatch"].t0_ns
        assert spans["dispatch"].t0_ns <= spans["collect"].t0_ns
        assert spans["collect"].t0_ns <= spans["finalize"].t0_ns
        # distinct request ids per submission
    ids = {f.result().trace.request_id for f in futs}
    assert len(ids) == 3
    session.close()


def test_dispatch_spans_reach_the_profiler_once(recorder):
    """The request's program spans are all opened on the annotator, and the
    dispatch's host work splits into send-table stacking and the enqueue."""
    schema, kws = _crafted_schema(seed=0)
    session = FCTSession(schema, metrics=MetricsRegistry())
    resp = session.query(FCTRequest(keywords=tuple(kws), r_max=3))
    opened = recorder.opened()
    for name in ("plan", "dispatch", "engine.dispatch_group",
                 "store.send_tables", "engine.enqueue", "collect"):
        assert name in opened, (name, opened)
    assert opened.count("dispatch") == opened.count("collect") == 1
    spans = resp.trace.spans()
    groups = {s.span_id: s for s in spans
              if s.name == "engine.dispatch_group"}
    children = [s for s in spans
                if s.name in ("store.send_tables", "engine.enqueue")]
    assert groups and len(children) == 2 * len(groups)
    for s in children:
        g = groups[s.parent_id]
        assert g.t0_ns <= s.t0_ns and s.t0_ns + s.dur_ns <= g.t0_ns + g.dur_ns
    session.close()


def test_session_metrics_snapshot_counts_queries():
    schema, kws = _crafted_schema(seed=0)
    m = MetricsRegistry()
    # a private engine (cache_max_entries) registers the engine/cache
    # instruments into this session's registry instead of the process one
    session = FCTSession(schema, metrics=m,
                         config=SessionConfig(cache_max_entries=8))
    session.query(FCTRequest(keywords=tuple(kws), r_max=3))
    session.query(FCTRequest(keywords=tuple(kws), r_max=3))
    snap = m.snapshot()
    assert snap["counters"]["session.queries_served"] == 2
    assert snap["counters"]["engine.batches_run"] >= 1
    assert snap["counters"]["engine.bytes_shipped"] > 0
    assert snap["counters"]["store.uploads"] >= 1
    session.close()


# -- sinks --------------------------------------------------------------------

def test_json_lines_reporter(tmp_path):
    m = MetricsRegistry()
    c = m.counter("r.count")
    out = tmp_path / "metrics.jsonl"
    rep = JsonLinesReporter(m, str(out), interval_s=3600.0)  # no timer fire
    c.inc(5)
    rep.close()                           # writes the final snapshot line
    lines = out.read_text().splitlines()
    assert lines
    last = json.loads(lines[-1])
    assert last["metrics"]["counters"]["r.count"] == 5
    assert "ts" in last
    rep.close()                           # idempotent
