"""Snowflake schemas (one level of sub-dimensions) served natively: tree
candidate networks, the hop in the per-CN store programs, and exactness on
every path that accepts such a tenant.

The toy warehouse is ``repro.data.tpch.generate_snowflake``: LINEITEM to
PART, SUPPLIER and ORDERS, CUSTOMER behind ORDERS by ``custkey``.  Answers
are compared bin for bin with the program's tree oracle (``fct_star``), a
materialising brute force (``fct_bruteforce``) and the benchmark's own
reference (``bench/reference/snowflake_oracle.py``), which shares no code
with ``src/``.
"""
import functools
import itertools
import operator
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from repro.api import FCTRequest, FCTSession, SessionConfig
from repro.core.candidate_network import (TupleSets, enumerate_cns,
                                          enumerate_star_cns, prune_empty_cns)
from repro.core.fct import make_fct_program, run_cn_plan_two_jobs
from repro.core.plan import build_cn_plan
from repro.core.star import fct_bruteforce, fct_star, schema_cns, topk_terms
from repro.data.schema import Relation, StarSchema, SubEdge
from repro.data.tpch import (TpchConfig, generate, generate_snowflake,
                             plant_keywords)
from repro.launch.mesh import make_worker_mesh
from repro.obs import Trace
from repro.runtime.cache import ExecutableCache
from repro.runtime.engine import FCTEngine
from repro.serve import Gateway, GatewayConfig, SchemaRegistry

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from bench.reference.snowflake_oracle import Reference  # noqa: E402

SEG, ORD, PRT = 33, 32, 30        # CUSTOMER, ORDERS and PART keywords
CFG = TpchConfig(fact_rows=240, part_rows=20, supp_rows=12, order_rows=30,
                 cust_rows=12, text_len=5, vocab_size=40, seed=3)


def snowflake():
    return plant_keywords(generate_snowflake(CFG), {
        "CUSTOMER": [SEG], "ORDERS": [ORD], "PART": [PRT]}, frac=0.3)


def bench_view(schema: StarSchema):
    """The program's snowflake as the benchmark reference reads a
    warehouse (every dimension's key of row r is r)."""
    m = schema.m
    dims = [SimpleNamespace(name=d.name, key=e.fact_col, text=d.text)
            for d, e in zip(schema.dims[:m], schema.edges)]
    star = SimpleNamespace(fact_text=schema.fact.text, dims=dims,
                           fact_keys={e.fact_col: schema.fact_keys(i)
                                      for i, e in enumerate(schema.edges)})
    subs = [SimpleNamespace(text=schema.dims[m + j].text,
                            parent=se.parent_name,
                            parent_keys=schema.parent_fk(m + j))
            for j, se in enumerate(schema.sub_edges)]
    return SimpleNamespace(star=star, subs=subs, vocab=schema.vocab_size)


# -- candidate networks --------------------------------------------------------

def _or(masks):
    return functools.reduce(operator.or_, masks, 0)


def _brute_cns(n_kw, parents, r_max):
    """Connected subtrees (nodes: -1 the fact, then each dimension) x every
    mask per node, kept when total and every leaf holds a keyword no other
    node holds."""
    full = (1 << n_kw) - 1
    nodes_all = [-1] + list(range(len(parents)))
    up = {i: p for i, p in enumerate(parents)}
    out = set()
    for size in range(1, min(r_max, len(nodes_all)) + 1):
        for nodes in itertools.combinations(nodes_all, size):
            edges = [(v, up[v]) for v in nodes if v >= 0 and up[v] in nodes]
            if len(edges) != size - 1:
                continue
            deg = {v: sum(v in e for e in edges) for v in nodes}
            for masks in itertools.product(range(full + 1), repeat=size):
                mk = dict(zip(nodes, masks))
                if _or(masks) != full:
                    continue
                ok = size == 1 or all(
                    mk[v] & ~_or(mk[u] for u in nodes if u != v)
                    for v in nodes if deg[v] <= 1)
                if ok:
                    out.add((mk.get(-1, -1), tuple(mk.get(i) for i in
                                                   range(len(parents)))))
    return out


@pytest.mark.parametrize("parents", [(-1, -1, -1, 2), (-1, -1, 0, 1),
                                     (-1, 0, 0), (-1, -1, -1)])
@pytest.mark.parametrize("n_kw", [1, 2, 3])
def test_tree_cns_equal_brute_force_subtrees_times_masks(parents, n_kw):
    for r_max in range(1, 6):
        cns = enumerate_cns(n_kw, parents, r_max)
        got = []
        for cn in cns:
            if cn.single_dim >= 0:
                masks = [None] * len(parents)
                masks[cn.single_dim] = (1 << n_kw) - 1
                got.append((-1, tuple(masks)))
            else:
                got.append((cn.fact_mask, cn.dim_masks))
            assert cn.n_relations() <= r_max
        assert len(got) == len(set(got)), "duplicate CN"
        assert set(got) == _brute_cns(n_kw, parents, r_max), r_max


def _seed_star_cns(n_keywords, m_dims, r_max):
    """The star enumerator the tree enumerator replaced, verbatim in
    effect: its order is what cached plans were built in."""
    full = (1 << n_keywords) - 1
    out = []
    if r_max >= 1:
        out.append((full, (None,) * m_dims, -1))
        out.extend((-1, (None,) * m_dims, i) for i in range(m_dims))
    for r in range(m_dims + 1):
        for leaves in itertools.combinations(range(m_dims), r):
            if not leaves or 1 + len(leaves) > r_max:
                continue
            for fm in range(full + 1):
                for lms in itertools.product(range(1, full + 1),
                                             repeat=len(leaves)):
                    if fm | _or(lms) != full:
                        continue
                    if any(fm | _or(x for j, x in enumerate(lms) if j != i)
                           == full for i in range(len(lms))):
                        continue
                    if len(lms) == 1 and lms[0] == full:
                        continue
                    dm = [None] * m_dims
                    for leaf, lm in zip(leaves, lms):
                        dm[leaf] = lm
                    out.append((fm, tuple(dm), -1))
    return out


def test_star_schema_enumerates_the_seed_list_in_order():
    for n_kw, m, r_max in itertools.product(range(1, 4), range(0, 5),
                                            range(0, 6)):
        got = [(c.fact_mask, c.dim_masks, c.single_dim)
               for c in enumerate_star_cns(n_kw, m, r_max)]
        assert got == _seed_star_cns(n_kw, m, r_max), (n_kw, m, r_max)
        assert got == [(c.fact_mask, c.dim_masks, c.single_dim)
                       for c in enumerate_cns(n_kw, (-1,) * m, r_max)]


def test_schema_refuses_a_deeper_tree_and_a_cycle():
    sf = generate_snowflake(CFG)
    cust = sf.dims[3]
    deeper = Relation("NATION", keys={"nationkey": np.arange(4, dtype=np.int32)},
                      key_domains={"nationkey": 4}, text=cust.text[:4])
    cust2 = Relation(cust.name, keys={**cust.keys, "nationkey": np.zeros(
        cust.rows, np.int32)}, key_domains={**cust.key_domains,
                                            "nationkey": 4}, text=cust.text)
    with pytest.raises(ValueError, match="one level"):
        StarSchema(fact=sf.fact, dims=[*sf.dims[:3], cust2, deeper],
                   edges=sf.edges, vocab_size=sf.vocab_size,
                   sub_edges=[*sf.sub_edges, SubEdge(
                       "NATION", "CUSTOMER", "nationkey", "nationkey")])
    # CUSTOMER behind both ORDERS and PART closes a cycle through the fact
    with pytest.raises(ValueError, match="repeat"):
        StarSchema(fact=sf.fact, dims=[*sf.dims, cust], edges=sf.edges,
                   vocab_size=sf.vocab_size,
                   sub_edges=[*sf.sub_edges, SubEdge(
                       "CUSTOMER", "PART", "partkey", "custkey")])


# -- answers -------------------------------------------------------------------

QUERIES = [((SEG,), 2), ((SEG, ORD), 3), ((SEG, PRT), 4),
           ((SEG, PRT, ORD), 5), ((ORD, 7), 3)]


@pytest.fixture(scope="module")
def schema():
    return snowflake()


def test_tree_oracles_agree(schema):
    ref = Reference(bench_view(schema))
    for kws, r_max in QUERIES:
        want = fct_star(schema, kws, r_max)
        assert want[1:].sum() > 0
        np.testing.assert_array_equal(want, fct_bruteforce(schema, kws,
                                                           r_max))
        np.testing.assert_array_equal(want, ref.histogram(kws, r_max))


def test_gateway_serves_a_snowflake_tenant_exactly(schema):
    registry = SchemaRegistry(mesh=make_worker_mesh(1))
    registry.register("snow", schema)
    gw = Gateway(registry, GatewayConfig(result_cache_ttl_s=0))
    try:
        futs = [(kws, r, gw.submit("snow", FCTRequest(
            keywords=kws, r_max=r, top_k=6))) for kws, r in QUERIES]
        hop = 0
        for kws, r_max, fut in futs:
            resp = fut.result(timeout=120)
            want = fct_star(schema, kws, r_max)
            np.testing.assert_array_equal(resp.all_freqs, want)
            ids, f = topk_terms(want, kws, 6)
            np.testing.assert_array_equal(resp.term_ids, ids)
            np.testing.assert_array_equal(resp.freqs, f)
            hop += resp.engine_stats["hop_rows"]
        assert hop > 0
        assert registry.session("snow").stats()["hop_rows"] > 0
    finally:
        gw.close()
        registry.close()


def test_hop_rows_rides_the_enqueue_span_and_is_zero_on_a_star(schema):
    engine = FCTEngine(cache=ExecutableCache())
    s = FCTSession(schema, engine=engine)
    trace = Trace()
    resp = s._execute(s._plan(FCTRequest(keywords=(SEG, PRT), r_max=4),
                              trace=trace))
    spans = [sp for sp in trace.spans() if sp.name == "engine.enqueue"]
    assert sum(sp.args["hop_rows"] for sp in spans) == \
        resp.engine_stats["hop_rows"] > 0
    star = plant_keywords(generate(CFG), {"PART": [PRT]}, frac=0.3)
    before = engine.stats()["hop_rows"]
    FCTSession(star, engine=engine).query(FCTRequest(keywords=(PRT, 7),
                                                     r_max=3))
    assert engine.stats()["hop_rows"] == before


def test_device_topk_is_exact_on_a_snowflake(schema):
    s = FCTSession(schema, config=SessionConfig(device_topk=True))
    for kws, r_max in QUERIES[:4]:
        resp = s.query(FCTRequest(keywords=kws, r_max=r_max, top_k=5))
        assert resp.finalize == ("device_topk" if resp.n_joined_cns
                                 else "host")
        ids, f = topk_terms(fct_star(schema, kws, r_max), kws, 5)
        np.testing.assert_array_equal(resp.freqs, f)
        np.testing.assert_array_equal(resp.term_ids, ids)


def _append_rows(schema, relation, n, rng, kw):
    """n rows for ``relation``: keys in range (new keys for a dimension's
    own key), text holding ``kw`` in every other row."""
    role, i = schema.relation_role(relation)
    rel = schema.fact if role == "fact" else schema.dims[i]
    own = None if role == "fact" else schema.edges[i].dim_col \
        if i < schema.m else schema.sub_edges[i - schema.m].child_col
    rows = []
    for r in range(n):
        row = {}
        for col, dom in rel.key_domains.items():
            row[col] = rel.rows + r if col == own else int(rng.integers(dom))
        text = rng.integers(1, 30, size=rel.text_len)
        if r % 2 == 0:
            text[0] = kw
        row["text"] = text.tolist()
        rows.append(row)
    return rows


def test_appends_to_customer_and_orders_are_answered_exactly():
    schema = snowflake()
    rng = np.random.default_rng(11)
    registry = SchemaRegistry(mesh=make_worker_mesh(1))
    registry.register("snow", schema)
    gw = Gateway(registry, GatewayConfig(result_cache_ttl_s=3600))
    qs = [((SEG, ORD), 3), ((SEG, PRT), 4)]
    try:
        for kws, r in qs:                 # cache the pre-append answers
            gw.submit("snow", FCTRequest(keywords=kws, r_max=r)).result(120)
        for rel, kw in (("CUSTOMER", SEG), ("ORDERS", ORD),
                        ("CUSTOMER", ORD)):
            cur = registry.session("snow").schema
            gw.append("snow", rel, _append_rows(cur, rel, 4, rng, kw))
            cur = registry.session("snow").schema
            for kws, r in qs:
                resp = gw.submit("snow", FCTRequest(
                    keywords=kws, r_max=r)).result(120)
                np.testing.assert_array_equal(resp.all_freqs,
                                              fct_star(cur, kws, r))
                np.testing.assert_array_equal(
                    resp.all_freqs, fct_bruteforce(cur, kws, r))
        assert cur.dims[3].rows == CFG.cust_rows + 8
    finally:
        gw.close()
        registry.close()


def test_host_stacked_and_seed_families_refuse_a_hop_plan(schema):
    kws = (SEG, PRT)
    ts = TupleSets.build(schema, kws)
    plans = [build_cn_plan(schema, ts, cn, 1) for cn in prune_empty_cns(
        schema_cns(schema, len(kws), 4), ts)]
    hop = [p for p in plans if p is not None and p.hops]
    assert hop
    mesh = make_worker_mesh(1)
    engine = FCTEngine(cache=ExecutableCache())
    with pytest.raises(NotImplementedError):
        engine.run_plans(hop, mesh)                 # no store: fct_batched
    with pytest.raises(NotImplementedError):
        engine.run_plans_individual(hop, mesh)      # fct_batched_percn
    with pytest.raises(NotImplementedError):
        make_fct_program(hop[0], mesh)
    with pytest.raises(NotImplementedError):
        run_cn_plan_two_jobs(hop[0], mesh)
