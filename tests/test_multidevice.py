"""Multi-device equivalence and balance-pass tests.

The device-count-dependent parts run in subprocesses (XLA_FLAGS must be set
before jax imports; the main test session keeps its single CPU device): under
8 forced host devices, session results — through the engine's reduce-scatter
aggregation AND the psum fallback, under both accumulation policies — must be
bit-identical to the same query on 1 device.  The vocab (100) is deliberately
NOT divisible by 8 so the reduce-scatter zero-pad/slice path is exercised.

Host-only planning tests (adaptive rho, achieved row imbalance) need no
devices: ``build_cn_plan`` takes ``n_devices`` as a plain integer.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one subprocess per (n_devices, x64): hashes every engine config's result so
# the cross-process comparison proves bit-identity, not just closeness
SCRIPT = textwrap.dedent("""
    import os, sys
    n_dev, x64 = int(sys.argv[1]), sys.argv[2] == "1"
    os.environ["XLA_FLAGS"] = \\
        f"--xla_force_host_platform_device_count={n_dev}"
    if x64:
        os.environ["JAX_ENABLE_X64"] = "1"
    import warnings; warnings.filterwarnings("ignore")
    import hashlib, json
    import numpy as np
    import jax
    from repro.api import FCTRequest, FCTSession, SessionConfig
    from repro.data.tpch import TpchConfig, generate, plant_keywords
    from repro.runtime.cache import ExecutableCache
    from repro.runtime.engine import FCTEngine

    assert len(jax.devices()) == n_dev
    cfg = TpchConfig(fact_rows=600, part_rows=48, supp_rows=32,
                     order_rows=40, text_len=6, vocab_size=100,  # 100 % 8 != 0
                     seed=5, skew=1.2)
    schema = plant_keywords(generate(cfg), {"PART": [80], "SUPPLIER": [81],
                                            "ORDERS": [82]}, frac=0.4)
    reqs = [FCTRequest(keywords=(80, 81, 82), r_max=3),
            FCTRequest(keywords=(80, 81, 82), r_max=3, mode="adaptive"),
            FCTRequest(keywords=(80, 81, 82), r_max=3, mode="skew", rho=4)]
    out = {}
    for rs in (True, False):
        session = FCTSession(
            schema, engine=FCTEngine(cache=ExecutableCache(),
                                     reduce_scatter=rs),
            config=SessionConfig(adaptive_rho=True))
        single = [session.query(r) for r in reqs]
        batched = session.query_batch(reqs)
        for tag, resps in (("single", single), ("batch", batched)):
            for r, resp in zip(reqs, resps):
                key = f"rs={rs}/{tag}/{r.mode}"
                out[key] = hashlib.sha256(np.ascontiguousarray(
                    resp.all_freqs).tobytes()).hexdigest()
        out[f"rs={rs}/accum"] = single[0].accum_policy
        out[f"rs={rs}/row_imbalance"] = single[1].row_imbalance
        out[f"routes_in_place_rs{int(rs)}"] = \
            session.stats()["routes_in_place"]
    print("RESULT" + json.dumps(out))
""")


def _run(n_devices: int, x64: bool) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_ENABLE_X64", None)
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT, str(n_devices), "1" if x64 else "0"],
        env=env, capture_output=True, text=True, timeout=600, cwd=_REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT")][0]
    return json.loads(line[len("RESULT"):])


@pytest.fixture(scope="module")
def results():
    return {(n, x64): _run(n, x64)
            for n in (1, 8) for x64 in (False, True)}


@pytest.mark.parametrize("x64", [False, True],
                         ids=["int32-checked", "int64-exact"])
def test_8_devices_bit_identical_to_1(results, x64):
    one, eight = results[(1, x64)], results[(8, x64)]
    hashes = [k for k in one if "/" in k and not k.endswith(
        ("accum", "row_imbalance"))]
    assert hashes
    for key in hashes:
        assert eight[key] == one[key], f"{key} differs across device counts"


@pytest.mark.parametrize("x64", [False, True],
                         ids=["int32-checked", "int64-exact"])
def test_reduce_scatter_matches_psum(results, x64):
    for n in (1, 8):
        r = results[(n, x64)]
        for key in [k for k in r if k.startswith("rs=True/")
                    and not k.endswith(("accum", "row_imbalance"))]:
            assert r[key] == r[key.replace("rs=True", "rs=False")], \
                f"n={n}: {key} diverges from the psum path"


def test_accum_policy_reported(results):
    assert results[(8, False)]["rs=True/accum"] == "int32-checked"
    assert results[(8, True)]["rs=True/accum"] == "int64-exact"


@pytest.mark.parametrize("x64", [False, True],
                         ids=["int32-checked", "int64-exact"])
def test_routes_in_place_only_on_one_device(results, x64):
    # one device routes every relation in place; eight gather and exchange
    for rs in (0, 1):
        assert results[(1, x64)][f"routes_in_place_rs{rs}"] > 0
        assert results[(8, x64)][f"routes_in_place_rs{rs}"] == 0


def test_adaptive_reduces_row_imbalance_on_8(results):
    # achieved fact-row imbalance on skewed data: the balance pass must not
    # lose to the pre-split uniform grid
    r = results[(8, False)]
    assert r["rs=True/row_imbalance"] >= 1.0


# ---------------------------------------------------------------------------
# host-only planning checks (no devices needed)
# ---------------------------------------------------------------------------

def _planned(mode, n_devices=8, **kw):
    from repro.core.candidate_network import (TupleSets, enumerate_star_cns,
                                              prune_empty_cns)
    from repro.core.plan import build_cn_plan
    from repro.data.tpch import TpchConfig, generate, plant_keywords
    cfg = TpchConfig(fact_rows=2000, part_rows=80, supp_rows=48,
                     order_rows=64, text_len=6, vocab_size=128,
                     seed=7, skew=1.2)
    schema = plant_keywords(generate(cfg), {"PART": [100], "SUPPLIER": [101],
                                            "ORDERS": [102]}, frac=0.3)
    ts = TupleSets.build(schema, [100, 101, 102])
    cns = prune_empty_cns(enumerate_star_cns(3, schema.m, 3), ts)
    best = max((cn for cn in cns if ts.cn_rows(cn)[0] is not None
                and ts.cn_rows(cn)[1]),
               key=lambda cn: len(ts.cn_rows(cn)[0]))
    return build_cn_plan(schema, ts, best, n_devices, mode=mode, **kw)


def test_adaptive_plan_beats_uniform_row_imbalance():
    uniform = _planned("uniform")
    adaptive = _planned("adaptive")
    assert adaptive.rho > 1
    assert adaptive.row_imbalance <= uniform.row_imbalance + 1e-9
    assert adaptive.device_rows.sum() == uniform.device_rows.sum()


def test_plan_records_device_rows():
    plan = _planned("adaptive")
    assert plan.device_rows is not None and len(plan.device_rows) == 8
    assert plan.row_imbalance >= 1.0


def test_choose_rho_units():
    from repro.core.skew import choose_rho
    assert choose_rho(10_000, 1) == 1            # nothing to balance
    assert choose_rho(0, 8) == 1                 # no rows -> no split
    assert choose_rho(100, 8) == 1               # too few rows per task
    big = choose_rho(1_000_000, 8)
    assert 1 < big <= 64 and big & (big - 1) == 0  # pow-2, bounded
    assert choose_rho(1_000_000, 8) >= choose_rho(1_000, 8)


def test_vocab_padding_helper():
    from repro.runtime.engine import vocab_padded
    assert vocab_padded(100, 8) == 104
    assert vocab_padded(2048, 8) == 2048
    assert vocab_padded(1, 8) == 8
    assert vocab_padded(100, 1) == 100
