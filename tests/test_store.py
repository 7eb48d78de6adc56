"""Device-resident relation store: bit-exact equivalence with the seed
per-CN path, upload-once reuse across warm queries and batch compositions,
byte-budget eviction, invalidation and x64-flag keying."""
import jax
import numpy as np
import pytest

from repro.api import FCTRequest, FCTSession, SessionConfig
from repro.core.candidate_network import (TupleSets, enumerate_star_cns,
                                          prune_empty_cns)
from repro.core.fct import run_cn_plan
from repro.core.plan import build_cn_plan, lane_major
from repro.core.star import fct_star
from repro.launch.mesh import make_worker_mesh
from repro.runtime.engine import FCTEngine
from repro.runtime.store import RelationStore

from test_engine import _crafted_schema, _dataset


def _joined_plans(schema, kws, r_max, n_dev):
    ts = TupleSets.build(schema, kws)
    cns = prune_empty_cns(enumerate_star_cns(len(kws), schema.m, r_max), ts)
    return [p for p in (build_cn_plan(schema, ts, cn, n_dev) for cn in cns)
            if p is not None]


@pytest.fixture
def x64(request):
    # force the requested mode explicitly either way: under the CI x64 job
    # (JAX_ENABLE_X64=1) the process STARTS in x64 mode, and the "int32"
    # parametrization must still exercise the int32 accumulator path
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", bool(request.param))
    yield bool(request.param)
    jax.config.update("jax_enable_x64", prev)


@pytest.mark.parametrize("x64", [False, True], indirect=True,
                         ids=["int32", "x64"])
@pytest.mark.parametrize("dataset", ["star_crafted", "tpch_star"])
def test_store_path_bit_identical_to_seed_engine(dataset, x64):
    # the store-resident data path must reproduce the pre-refactor engine
    # (host-stacked columns) and the seed per-CN path bit-for-bit, on the
    # crafted star schema and the TPC-H-like dataset, in both int dtypes
    if dataset == "star_crafted":
        schema, kws = _crafted_schema(seed=0)
    else:
        schema, kws = _dataset("star")
    mesh = make_worker_mesh()
    plans = _joined_plans(schema, kws, 3, mesh.devices.size)
    assert plans, "dataset produced no joined CNs"
    seed = sum(run_cn_plan(p, mesh) for p in plans)
    legacy = FCTEngine().run_plans(plans, mesh)               # host-stacked
    store_eng = FCTEngine()
    store = RelationStore(mesh)
    via_store = store_eng.run_plans(plans, mesh, store=store)  # resident
    np.testing.assert_array_equal(legacy, seed)
    np.testing.assert_array_equal(via_store, seed)
    assert store.uploads > 0
    assert store_eng.column_bytes_shipped == 0, \
        "store path shipped host relation columns"
    # per-CN-output family reuses the same uploads and stays exact
    uploads = store.uploads
    indiv = store_eng.run_plans_individual(plans, mesh, store=store)
    np.testing.assert_array_equal(indiv.sum(axis=0), seed)
    assert store.uploads == uploads, "program families re-uploaded columns"


def test_store_reuse_across_warm_queries_and_salts():
    schema, kws = _crafted_schema(seed=0)
    session = FCTSession(schema, engine=FCTEngine())
    cold = session.query(FCTRequest(keywords=tuple(kws), r_max=3))
    assert cold.engine_stats["store_uploads"] > 0
    assert cold.engine_stats["store_upload_bytes"] == \
        session.store.resident_bytes
    # same keywords, different routing (salt) or schedule (mode): the send
    # tables change but the tuple-set COLUMNS are identical — zero uploads
    for req in (FCTRequest(keywords=tuple(kws), r_max=3),
                FCTRequest(keywords=tuple(kws), r_max=3, salt=1),
                FCTRequest(keywords=tuple(kws), r_max=3, mode="skew")):
        warm = session.query(req)
        assert warm.engine_stats["store_uploads"] == 0, req
        assert warm.engine_stats["store_hits"] > 0
    np.testing.assert_array_equal(
        session.query(FCTRequest(keywords=tuple(kws), r_max=3)).all_freqs,
        cold.all_freqs)


def test_store_reuse_across_batch_compositions():
    # the retired stack cache only helped deterministic single-query group
    # compositions; the content-addressed store is composition-independent
    schema, kws = _crafted_schema(seed=0)
    session = FCTSession(schema, engine=FCTEngine())
    r1 = FCTRequest(keywords=tuple(kws), r_max=3)
    r2 = FCTRequest(keywords=tuple(kws), r_max=3, salt=1)
    r3 = FCTRequest(keywords=tuple(kws), r_max=2)
    want = {r: session.query(r).all_freqs for r in (r1, r2, r3)}
    uploads = session.store.uploads
    for batch in ([r1, r2], [r2, r3, r1], [r3, r1]):
        responses = session.query_batch(batch)
        assert session.store.uploads == uploads, \
            f"batch {batch} re-uploaded store-resident columns"
        for req, resp in zip(batch, responses):
            np.testing.assert_array_equal(resp.all_freqs, want[req])


def test_store_byte_budget_evicts_lru():
    schema, kws = _crafted_schema(seed=0)
    mesh = make_worker_mesh()
    plans = _joined_plans(schema, kws, 3, mesh.devices.size)
    # measure the unbounded footprint, then rerun with half the budget
    probe = RelationStore(mesh)
    FCTEngine().run_plans(plans, mesh, store=probe)
    budget = probe.resident_bytes // 2
    store = RelationStore(mesh, max_bytes=budget)
    engine = FCTEngine()
    out = engine.run_plans(plans, mesh, store=store)
    np.testing.assert_array_equal(
        out, FCTEngine().run_plans(plans, mesh))
    assert store.evictions > 0, "half-budget store never evicted"
    assert store.resident_bytes <= max(
        budget, max(e.nbytes for e in store._entries.values()))
    # evicted entries re-upload on the next dispatch — still correct
    uploads = store.uploads
    out2 = engine.run_plans(plans, mesh, store=store)
    np.testing.assert_array_equal(out2, out)
    assert store.uploads > uploads, "evicted columns were never re-uploaded"
    with pytest.raises(ValueError, match="max_bytes"):
        RelationStore(mesh, max_bytes=0)


def test_session_invalidate_drops_device_buffers():
    schema, kws = _crafted_schema(seed=0)
    session = FCTSession(schema, engine=FCTEngine(),
                         config=SessionConfig(store_max_bytes=1 << 20))
    assert session.store.max_bytes == 1 << 20  # config plumbed through
    r1 = session.query(FCTRequest(keywords=tuple(kws), r_max=3))
    assert len(session.store) > 0 and session.store.resident_bytes > 0
    dropped = session.invalidate()
    assert dropped["store_entries"] > 0 and dropped["tuple_sets"] > 0
    assert len(session.store) == 0 and session.store.resident_bytes == 0
    # next query re-derives everything and still answers correctly
    r2 = session.query(FCTRequest(keywords=tuple(kws), r_max=3))
    assert r2.engine_stats["store_uploads"] > 0
    np.testing.assert_array_equal(r1.all_freqs, r2.all_freqs)
    np.testing.assert_array_equal(r2.all_freqs, fct_star(schema, kws, 3))


def test_session_invalidate_fences_inflight_planning(monkeypatch):
    # a tuple set / routing plan BUILT from pre-mutation data must not
    # re-enter the session caches when invalidate() lands mid-build (same
    # fence as RelationStore.epoch and the gateway's result generation)
    schema, kws = _crafted_schema(seed=0)
    session = FCTSession(schema, engine=FCTEngine())
    orig = TupleSets.build

    def build_then_invalidate(schema_, keywords):
        ts = orig(schema_, keywords)
        session.invalidate()        # the "mutation" overtakes this build
        return ts

    monkeypatch.setattr(TupleSets, "build", build_then_invalidate)
    r1 = session.query(FCTRequest(keywords=tuple(kws), r_max=3))
    st = session.stats()
    assert st["tuple_set_entries"] == 0, "stale tuple set re-entered cache"
    assert st["plan_entries"] == 0, "stale routing plan re-entered cache"
    monkeypatch.setattr(TupleSets, "build", orig)
    r2 = session.query(FCTRequest(keywords=tuple(kws), r_max=3))
    assert session.stats()["tuple_set_entries"] == 1  # fresh build cached
    np.testing.assert_array_equal(r1.all_freqs, r2.all_freqs)
    np.testing.assert_array_equal(r2.all_freqs, fct_star(schema, kws, 3))


def test_store_keys_on_x64_flag():
    # arrays uploaded under one x64 mode must not be served under the other
    # (the engine's programs are keyed the same way); start from explicit
    # int32 so the test also holds under the CI x64 job's environment
    schema, kws = _crafted_schema(seed=0)
    mesh = make_worker_mesh()
    plans = _joined_plans(schema, kws, 3, mesh.devices.size)
    store = RelationStore(mesh)
    engine = FCTEngine()
    prev = jax.config.jax_enable_x64
    try:
        jax.config.update("jax_enable_x64", False)
        i32 = engine.run_plans(plans, mesh, store=store)
        entries_i32 = len(store)
        uploads = store.uploads
        jax.config.update("jax_enable_x64", True)
        x64 = engine.run_plans(plans, mesh, store=store)
        assert store.uploads > uploads, "x64 dispatch reused int32 entries"
        assert len(store) == 2 * entries_i32
        np.testing.assert_array_equal(i32, np.asarray(x64))
        # back on int32 the original entries still hit
        jax.config.update("jax_enable_x64", False)
        uploads = store.uploads
        np.testing.assert_array_equal(
            engine.run_plans(plans, mesh, store=store), i32)
        assert store.uploads == uploads
    finally:
        jax.config.update("jax_enable_x64", prev)


def test_plans_are_descriptors_not_copies():
    # the tentpole memory claim: a CNPlan references the base relation
    # arrays instead of owning sharded copies, and its lazy materialization
    # matches what the store uploads
    schema, kws = _crafted_schema(seed=0)
    (plan, *_) = _joined_plans(schema, kws, 3, 1)
    assert plan.fact.ref.base_text is schema.fact.text, \
        "plan copied the fact text"
    for i, route in plan.dims.items():
        assert route.ref.base_text is schema.dims[i].text
    # materialized legacy columns agree with the store-upload layout (the
    # store keeps them lane-major: rows on the last axis)
    text, keys = plan.fact.ref.store_columns(
        plan.fact.ref.shard_rows, plan.fact.ref.text_len)
    np.testing.assert_array_equal(text, lane_major(plan.fact.text))
    sel = plan.fact.ref.fact_key_shards(plan.fact.key_cols)
    np.testing.assert_array_equal(sel, plan.fact.keys)
    np.testing.assert_array_equal(keys[:, list(plan.fact.key_cols)],
                                  lane_major(sel))
