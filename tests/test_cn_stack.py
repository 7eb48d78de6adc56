"""The per-CN family's stack size (``engine.cn_stack``): a group of one or
two CNs runs exactly that many slots, a larger one a multiple of
``CN_BUCKET_MIN``.  The null slots a dispatch adds are counted
(``engine.pad_slots``), and no stack size changes an answer: every slot's
histogram equals the per-CN oracle and the old 4-slot dispatch bin for
bin, on a star and through a snowflake hop."""
import numpy as np
import pytest

from repro.core.candidate_network import TupleSets, prune_empty_cns
from repro.core.plan import build_cn_plan
from repro.core.star import schema_cns, star_cn_frequencies
from repro.data.schema import PAD_ID
from repro.data.tpch import TpchConfig, generate_snowflake, plant_keywords
from repro.launch.mesh import make_worker_mesh
from repro.obs import Trace
from repro.runtime import engine as engine_mod
from repro.runtime.cache import ExecutableCache
from repro.runtime.engine import CN_BUCKET_MIN, FCTEngine, cn_stack
from repro.runtime.store import RelationStore

from test_engine import _dataset

#: group size -> slots the per-CN family dispatches
SIZES = [(1, 1), (2, 2), (3, 4), (4, 4), (5, 8), (9, 12)]


def _old_stack(n):
    """The rule before ``cn_stack``: every group up to a multiple of 4."""
    return -(-n // CN_BUCKET_MIN) * CN_BUCKET_MIN


def _snowflake():
    cfg = TpchConfig(fact_rows=240, part_rows=20, supp_rows=12,
                     order_rows=30, cust_rows=12, text_len=5, vocab_size=40,
                     seed=3)
    schema = plant_keywords(generate_snowflake(cfg), {
        "CUSTOMER": [33], "PART": [30]}, frac=0.3)
    return schema, [33, 30]


def _fact_wide_plan(kind):
    """The joined CN through the fact (for the snowflake, through the hop)
    with the most oracle mass, and its oracle histogram."""
    schema, kws = _dataset("star") if kind == "star" else _snowflake()
    ts = TupleSets.build(schema, kws)
    found = []
    for cn in prune_empty_cns(schema_cns(schema, len(kws), 4), ts):
        plan = build_cn_plan(schema, ts, cn, 1)
        if plan is None or not cn.has_fact:
            continue
        if kind == "snowflake" and not plan.hops:
            continue
        want = star_cn_frequencies(schema, ts, cn)
        want[PAD_ID] = 0
        found.append((want.sum(), plan, want))
    mass, plan, want = max(found, key=lambda f: f[0])
    assert mass > 0
    return plan, want


class Case:
    """One fact-wide plan, its oracle, and a one-device mesh and store."""

    def __init__(self, plan, want, mesh, store):
        self.plan, self.want, self.mesh, self.store = plan, want, mesh, store

    def run(self, engine, n):
        """One per-CN store dispatch of ``n`` copies of the plan: the
        per-slot histograms and the dispatch's spans."""
        trace = Trace()
        with trace.activate():
            out = engine.run_plans_individual([self.plan] * n, self.mesh,
                                              store=self.store)
        spans = trace.spans()
        stacks = [s.args["n_stack"] for s in spans
                  if s.name == "store.send_tables"]
        enq = [s.args for s in spans if s.name == "engine.enqueue"]
        assert len(stacks) == len(enq) == 1, "one signature group expected"
        assert enq[0]["kind"] == "fct_store_percn"
        out[:, PAD_ID] = 0
        return out, stacks[0], enq[0]["pad_slots"]


@pytest.fixture(scope="module", params=["star", "snowflake"])
def case(request):
    mesh = make_worker_mesh(1)
    plan, want = _fact_wide_plan(request.param)
    return Case(plan, want, mesh, RelationStore(mesh))


@pytest.mark.parametrize("n,slots", SIZES)
def test_group_size_sets_slots_pad_count_and_keeps_answers(case, n, slots,
                                                          monkeypatch):
    assert cn_stack(n) == slots
    engine = FCTEngine(cache=ExecutableCache())
    before = engine.stats()["pad_slots"]
    out, n_stack, pad = case.run(engine, n)
    assert n_stack == slots
    assert pad == engine.stats()["pad_slots"] - before == slots - n
    assert out.shape[0] == n
    for row in out:
        np.testing.assert_array_equal(row, case.want)
    monkeypatch.setattr(engine_mod, "cn_stack", _old_stack)
    old, old_stack, old_pad = case.run(engine, n)
    assert old_stack == _old_stack(n) and old_pad == _old_stack(n) - n
    np.testing.assert_array_equal(out, old)


def test_sizes_three_and_four_share_a_program_and_one_retraces_nothing(case):
    engine = FCTEngine(cache=ExecutableCache())
    case.run(engine, 3)
    traces = engine.cache.traces
    assert traces == 1
    case.run(engine, 4)
    assert engine.cache.traces == traces, "groups of 3 and 4 compiled twice"
    case.run(engine, 1)
    assert engine.cache.traces == traces + 1
    misses = engine.cache.misses
    out, n_stack, pad = case.run(engine, 1)
    assert (n_stack, pad) == (1, 0)
    assert engine.cache.traces == traces + 1, "a repeated group retraced"
    assert engine.cache.misses == misses
    np.testing.assert_array_equal(out[0], case.want)


def test_summed_family_keeps_exact_n_and_pads_nothing(case):
    engine = FCTEngine(cache=ExecutableCache())
    trace = Trace()
    with trace.activate():
        total = engine.run_plans([case.plan] * 3, case.mesh,
                                 store=case.store)
    total[PAD_ID] = 0
    np.testing.assert_array_equal(total, 3 * case.want)
    (stack,) = [s.args["n_stack"] for s in trace.spans()
                if s.name == "store.send_tables"]
    assert stack == 3
    assert engine.stats()["pad_slots"] == 0
