"""One-device routing is in place.

On a one-device mesh the planner keeps every routed row at its own send
slot (``send[0, 0, c]`` is ``c`` or -1), so the device route reads the
relation's own columns, cut or padded to the send capacity, instead of
gathering them and exchanging them with itself.  The answers must not move
by a single count.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import FCTRequest, FCTSession
from repro.core.candidate_network import (TupleSets, enumerate_star_cns,
                                          prune_empty_cns)
from repro.core.fct import _route
from repro.core.plan import build_cn_plan
from repro.core.star import fct_star
from repro.data.tpch import TpchConfig, generate, plant_keywords
from repro.runtime.cache import ExecutableCache
from repro.runtime.engine import FCTEngine
from repro.runtime.store import _null_send

#: keyword 203 is planted in 1% of the fact rows only: the CNs whose fact
#: tuple set carries it are sparse, so at rho >= 8 most of their tasks get
#: no fact row, LPT prunes them, and the dimension rows of buckets no live
#: task owns are sent nowhere
KWS = (200, 201, 202, 203)
R_MAX = 4


@pytest.fixture(scope="module")
def schema():
    cfg = TpchConfig(fact_rows=600, part_rows=48, supp_rows=32,
                     order_rows=40, text_len=6, vocab_size=256, seed=5)
    s = plant_keywords(generate(cfg), {"PART": [200], "SUPPLIER": [201],
                                       "ORDERS": [202],
                                       "LINEITEM": [200, 202]}, frac=0.4)
    return plant_keywords(s, {"LINEITEM": [203]}, frac=0.01, seed=3)


def _one_device_plans(schema, mode, rho=8):
    ts = TupleSets.build(schema, KWS)
    cns = prune_empty_cns(enumerate_star_cns(len(KWS), schema.m, R_MAX), ts)
    plans = [build_cn_plan(schema, ts, cn, 1, mode=mode, rho=rho)
             for cn in cns]
    return [p for p in plans if p is not None]


@pytest.mark.parametrize("mode", ["uniform", "skew", "adaptive",
                                  "round_robin"])
def test_one_device_send_tables_keep_rows_in_place(schema, mode):
    plans = _one_device_plans(schema, mode)
    assert plans
    holes = 0
    for plan in plans:
        for route in [plan.fact] + [plan.dims[i] for i in plan.included]:
            assert route.send.shape[:2] == (1, 1)
            table = route.send[0, 0]
            slot = np.arange(len(table))
            assert np.all((table == slot) | (table == -1)), route.ref.name
            assert np.count_nonzero(table >= 0) == route.sent_rows
            assert len(table) <= max(1, route.ref.shard_rows)
            # C is the last sent row + 1, so any -1 of a table that sends
            # something lies between sent rows
            holes += int(route.sent_rows > 0 and np.any(table < 0))
    if mode == "skew":
        # LPT's empty-task pruning dropped rows inside some table: the
        # case a compacted table would have put at the wrong slot
        assert holes > 0


def _gather_reference(text, keys, send):
    """The route as a gather over the send table (P = 1, no exchange)."""
    idx = np.maximum(send, 0).reshape(-1)
    return text[..., idx], keys[..., idx], (send >= 0).reshape(-1)


def _send(case, rows):
    rng = np.random.default_rng(11)
    if case == "holes":
        table = np.arange(rows, dtype=np.int32)
        table[rng.choice(rows, 5, replace=False)] = -1
    elif case == "null_slot":
        table = _null_send(1, rows)[0, 0]
    elif case == "cap_below_rows":
        table = np.arange(rows // 2, dtype=np.int32)
        table[[1, 6]] = -1
    else:  # cap_above_rows: slots past the last row are pad
        table = np.full((2 * rows,), -1, np.int32)
        table[:rows - 3] = np.arange(rows - 3)
    return table[None, :]


@pytest.mark.parametrize("key_shape", ["fact", "dim"])
@pytest.mark.parametrize("case", ["holes", "null_slot", "cap_below_rows",
                                  "cap_above_rows"])
def test_in_place_route_matches_gather(case, key_shape):
    rows, text_len = 16, 5
    rng = np.random.default_rng(3)
    text = rng.integers(1, 50, (text_len, rows), dtype=np.int32)
    keys = rng.integers(0, 9, (2, rows) if key_shape == "fact" else (rows,),
                        dtype=np.int32)
    send = _send(case, rows)
    rtext, rkeys, rmask = (np.asarray(a) for a in _route(
        jnp.asarray(text), jnp.asarray(keys), jnp.asarray(send)))
    ref_text, ref_keys, ref_mask = _gather_reference(text, keys, send)
    assert rtext.shape == ref_text.shape and rkeys.shape == ref_keys.shape
    np.testing.assert_array_equal(rmask, ref_mask)
    # a slot the mask drops carries weight 0 downstream: only sent slots
    # have to hold the sent row
    np.testing.assert_array_equal(rtext[:, rmask], ref_text[:, ref_mask])
    np.testing.assert_array_equal(rkeys[..., rmask], ref_keys[..., ref_mask])


def test_one_device_skew_query_matches_oracle(schema):
    session = FCTSession(schema, engine=FCTEngine(cache=ExecutableCache()))
    assert session.stats()["n_devices"] == 1
    before = session.stats()["routes_in_place"]
    resp = session.query(FCTRequest(keywords=KWS, r_max=R_MAX, mode="skew",
                                    rho=8))
    np.testing.assert_array_equal(resp.all_freqs,
                                  fct_star(schema, KWS, R_MAX))
    routed = sum(1 + len(p.included)
                 for p in _one_device_plans(schema, "skew"))
    assert routed > 0
    assert session.stats()["routes_in_place"] - before == routed
    assert resp.engine_stats["routes_in_place"] == routed
