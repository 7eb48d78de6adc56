"""The snowflake configuration on the CPU at a small size: its generator's
TPC-H rules, its reference against a materialising brute force and the
star reference, the ``segment_repeat`` traffic, the ``hop_rows`` reader,
and one run of the cell's traffic that comes out ``correct``."""
import itertools
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run  # noqa: E402
from bench.generators import segment_sets  # noqa: E402
from bench.metrics import hop_rows  # noqa: E402
from bench.reference import data, oracle, snowflake_oracle  # noqa: E402
from bench.schemas import snowflake  # noqa: E402
from bench.tests.test_bench_harness import small_config  # noqa: E402

CELL = "sf1-snowflake-segment-repeat"


def small_snowflake(customers=600):
    """``small_config``'s star (the same rows and pool as the star tests)
    with the snowflake's CUSTOMER behind ORDERS at ``customers`` rows."""
    cfg = small_config()
    snow = data.load_config("tpch-sf1-snowflake")
    cfg["schema"], cfg["subs"] = snow["schema"], snow["subs"]
    cfg["subs"][0]["rows"] = customers
    cfg["sub_pool_chars"] = 400_000
    return cfg


@pytest.fixture(scope="module")
def wh():
    return snowflake.generate(small_snowflake(), 2**31 + 5)


def test_config_is_tpch_sf1_with_customer_behind_orders():
    cfg, star = (data.load_config("tpch-sf1-snowflake"),
                 data.load_config("tpch-sf1"))
    for k in ("fact", "dims", "fact_keys", "text_pool_chars"):
        assert cfg[k] == star[k], k
    sub, = cfg["subs"]
    assert (sub["name"], sub["parent"], sub["rows"], sub["comment_chars"]) \
        == ("CUSTOMER", "ORDERS", 150_000, [29, 116])
    assert cfg["reduced"] == []
    cell, = [w for w in run.load_benchmark()["workloads"]
             if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tpch-sf1-snowflake", "segment_repeat", 1)


def test_generator_follows_tpch_rules(wh):
    cfg = small_snowflake()
    star = data.generate(cfg, 2**31 + 5)
    # the four star relations are tpch-sf1's, from the same seed
    assert np.array_equal(wh.star.fact_text, star.fact_text)
    for k, v in star.fact_keys.items():
        assert np.array_equal(wh.star.fact_keys[k], v)
    for a, b in zip(wh.star.dims, star.dims):
        assert np.array_equal(a.text, b.text)
    cust, = wh.subs
    orders = wh.star.dims[2]
    # 600 rows; 39 comment columns by (116 + 1) // 3, then the segment
    assert cust.text.shape == (600, 40)
    assert cust.parent_keys.shape == (orders.text.shape[0],)
    words = (cust.text[:, :39] != 0).sum(axis=1)
    assert words.min() >= 1 and words.max() <= 39
    segs = cust.text[:, 39]
    seg_ids = [wh.terms.index(w) for w in cfg["subs"][0]["segment_words"]]
    assert set(np.unique(segs)) == set(seg_ids)
    shares = np.bincount(segs, minlength=wh.vocab)[seg_ids] / 600
    assert np.all(np.abs(shares - 0.2) < 0.05), shares
    # O_CUSTKEY: never a multiple of 3, so a third of customers order
    # nothing; at ten orders a customer, as at SF1, every other one orders
    assert np.all(cust.parent_keys % 3 != 0)
    few = snowflake.generate(small_snowflake(customers=80), 2**31 + 5)
    ordered = np.zeros(80, bool)
    ordered[few.subs[0].parent_keys] = True
    assert np.array_equal(ordered, np.arange(80) % 3 != 0)
    # segment words are CUSTOMER's alone; the vocabulary is 210 + 5 + PAD
    assert wh.vocab == 216
    for name in wh.relations[:-1]:
        assert not np.isin(wh.relation_text(name), seg_ids).any(), name


def _brute(wh, kws, r_max):
    """Materialise every joined tuple tree of every CN and count its
    rows' terms (toy sizes only)."""
    texts, parents, fks = snowflake_oracle._tree(wh)
    masks = [np.zeros(t.shape[0], np.int64) for t in texts]
    for v, t in enumerate(texts):
        for b, kw in enumerate(kws):
            masks[v] |= (t == kw).any(axis=1).astype(np.int64) << b
    freq = np.zeros(wh.vocab, np.int64)
    for root, got in snowflake_oracle.tree_cns(len(kws), parents, r_max):
        sets = {v: np.nonzero(masks[v] == mk)[0] for v, mk in got.items()}

        def trees(v, r):
            out = [[(v, r)]]
            for c in got:
                if parents[c] == v:
                    opts = [t for s in sets[c] if s == fks[c][r]
                            for t in trees(c, s)]
                    out = [a + b for a in out for b in opts]
            return out
        for r in sets[root]:
            for tree in trees(root, r):
                for v, row in tree:
                    freq += np.bincount(texts[v][row], minlength=wh.vocab)
    freq[0] = 0
    return freq


def _toy(seed):
    """A hand-sized snowflake: fact 40 rows, dims 6 / 4 / 8, CUSTOMER 5
    behind ORDERS, vocabulary 12."""
    rng = np.random.default_rng(seed)
    text = lambda n, w: rng.integers(0, 12, size=(n, w)).astype(np.int32)
    dims = [data.Dim(n, k, text(r, 3)) for n, k, r in
            (("PART", "partkey", 6), ("SUPPLIER", "suppkey", 4),
             ("ORDERS", "orderkey", 8))]
    star = data.Warehouse("LINEITEM", text(40, 3),
                          {d.key: rng.integers(0, d.text.shape[0], 40)
                           .astype(np.int32) for d in dims}, dims, 12,
                          [str(i) for i in range(12)])
    cust = snowflake.Sub("CUSTOMER", "custkey", "ORDERS", text(5, 2),
                         rng.integers(0, 5, 8).astype(np.int32))
    return snowflake.Snowflake(star, [cust], 12, star.terms)


@pytest.mark.parametrize("seed", [1, 2])
def test_reference_equals_brute_force(seed):
    wh = _toy(seed)
    ref = snowflake_oracle.Reference(wh)
    for kws, r_max in itertools.product([(3,), (3, 5), (2, 7, 9)],
                                        [1, 2, 3, 4, 5]):
        assert np.array_equal(ref.histogram(kws, r_max),
                              _brute(wh, kws, r_max)), (kws, r_max)


def test_reference_on_a_star_equals_the_star_reference():
    star = data.generate(small_config(), 7)
    tree, flat = snowflake_oracle.Reference(star), oracle.Reference(star)
    g = {t: i for i, t in enumerate(star.terms)}
    for kws in [(g["boost"],), (g["special"], g["requests"]),
                (g["regular"], g["foxes"], g["sleep"])]:
        for r_max in (2, 4):
            assert np.array_equal(tree.histogram(kws, r_max),
                                  flat.histogram(kws, r_max))
            assert tree.cn_work(kws, r_max) == flat.cn_work(kws, r_max)


def test_segment_sets_check_bands_and_warm_up(wh):
    mix = run.load_mix("segment_repeat")
    t = segment_sets.make(mix, wh, np.random.default_rng(0))
    assert t.clients == 2
    assert t.warmup() == [[t.sets[0]], [t.sets[1]], list(t.sets)]
    for s, (seg, word) in zip(t.sets, t.shares):
        assert 0.15 <= seg < 0.25 and 0.005 <= word < 0.4
    bad = dict(mix, sets=[dict(mix["sets"][0], bands=["mid", "mid"])])
    with pytest.raises(ValueError, match="outside band"):
        segment_sets.make(bad, wh, None)
    # "boost" holds LINEITEM's band but is in every relation's comments
    bad = dict(mix, sets=[dict(mix["sets"][0], only_there=[True, True])])
    with pytest.raises(ValueError, match="alone"):
        segment_sets.make(bad, wh, None)


class _Span:
    """A program span as a reader sees it (hashed by identity)."""

    def __init__(self, name, **args):
        self.name, self.args = name, args


def test_hop_rows_reads_distinct_enqueue_spans_per_request():
    shared = [_Span("engine.enqueue", hop_rows=100, kind="fct_store_percn"),
              _Span("engine.enqueue", hop_rows=20, kind="fct_store_percn"),
              _Span("store.send_tables", n_stack=4)]
    lead = SimpleNamespace(trace=SimpleNamespace(spans=lambda: shared))
    other = SimpleNamespace(trace=SimpleNamespace(spans=lambda: []))
    recs = [SimpleNamespace(response=lead), SimpleNamespace(response=other),
            SimpleNamespace(response=lead)]
    assert hop_rows.read({"records": recs}) == 120 / 3
    # a program whose spans carry no hop_rows reads nothing
    old = [_Span("engine.enqueue", kind="fct_store_percn")]
    recs = [SimpleNamespace(response=SimpleNamespace(
        trace=SimpleNamespace(spans=lambda: old)))]
    assert hop_rows.read({"records": recs}) is None


def test_segment_repeat_runs_correct_on_the_cpu():
    bench = run.load_benchmark()
    res = run.run_cell(small_snowflake(300), run.load_mix("segment_repeat"),
                       chips=1, seed=2**31 + 41, seconds=1.5, trace=False,
                       metrics=bench["end_to_end"],
                       t_process=time.perf_counter())
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert all(c["value"] == 0 for c in res["checks"].values())
    assert set(res["metrics"]) == {"qps", "latency_p50_ms", "latency_p95_ms",
                                   "setup_s"}
