"""Property-based validation of the FCT engine's core invariants."""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")  # optional dev dependency
import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core.candidate_network import enumerate_star_cns
from repro.core.fct import run_fct_query
from repro.core.shares import closed_form_shares, optimize_shares, replication_cost
from repro.core.star import fct_bruteforce, fct_star
from repro.data.schema import JoinEdge, Relation, StarSchema, tokens_histogram
from repro.kernels.fct_count import ref as fct_ref
from repro.kernels.fct_count.ops import weighted_histogram

SETTINGS = dict(max_examples=20, deadline=None)


def random_schema(draw):
    rng = np.random.default_rng(draw(st.integers(0, 10_000)))
    vocab = 48
    m = draw(st.integers(1, 3))
    dim_rows = [draw(st.integers(2, 8)) for _ in range(m)]
    fact_rows = draw(st.integers(4, 24))
    text_len = 4
    dims, edges = [], []
    for i, rows in enumerate(dim_rows):
        dims.append(Relation(
            f"D{i}",
            keys={f"k{i}": np.arange(rows, dtype=np.int32)},
            key_domains={f"k{i}": rows},
            text=rng.integers(1, vocab, (rows, text_len)).astype(np.int32)))
        edges.append(JoinEdge(f"D{i}", f"k{i}", f"k{i}"))
    fact = Relation(
        "F",
        keys={f"k{i}": rng.integers(0, dim_rows[i], fact_rows)
              .astype(np.int32) for i in range(m)},
        key_domains={f"k{i}": dim_rows[i] for i in range(m)},
        text=rng.integers(1, vocab, (fact_rows, text_len)).astype(np.int32))
    return StarSchema(fact=fact, dims=dims, edges=edges, vocab_size=vocab)


@settings(**SETTINGS)
@given(st.data())
def test_star_equals_bruteforce_on_random_schemas(data):
    schema = random_schema(data.draw)
    n_kw = data.draw(st.integers(1, 2))
    kws = [40 + i for i in range(n_kw)]
    # plant keywords in random relations so tuple sets are non-trivial
    rng = np.random.default_rng(data.draw(st.integers(0, 10_000)))
    for rel in [schema.fact, *schema.dims]:
        for kw in kws:
            rows = rng.random(rel.rows) < 0.4
            idx = np.nonzero(rows)[0]
            rel.text[idx, rng.integers(0, rel.text_len, idx.size)] = kw
    r_max = data.draw(st.integers(1, schema.m + 1))
    np.testing.assert_array_equal(fct_bruteforce(schema, kws, r_max),
                                  fct_star(schema, kws, r_max))


@settings(**SETTINGS)
@given(st.data())
def test_distributed_equals_star_on_random_schemas(data):
    schema = random_schema(data.draw)
    kws = [40]
    rng = np.random.default_rng(7)
    for rel in [schema.fact, *schema.dims]:
        idx = np.nonzero(rng.random(rel.rows) < 0.5)[0]
        rel.text[idx, rng.integers(0, rel.text_len, idx.size)] = 40
    mode = data.draw(st.sampled_from(["uniform", "skew", "round_robin"]))
    res = run_fct_query(schema, kws, r_max=schema.m + 1, mode=mode, rho=2)
    np.testing.assert_array_equal(res.all_freqs,
                                  fct_star(schema, kws, schema.m + 1))


@settings(**SETTINGS)
@given(st.integers(1, 4), st.data())
def test_integer_shares_beat_random_factorizations(m, data):
    sizes = [data.draw(st.integers(1, 10_000)) for _ in range(m)]
    k = data.draw(st.sampled_from([4, 8, 9, 16, 27, 64, 256]))
    plan = optimize_shares(sizes, k)
    assert int(np.prod(plan.shares)) == k
    # integer optimum can't beat the fractional lower bound
    assert plan.cost >= plan.fractional_cost - 1e-6
    # and beats (or ties) arbitrary random integer factorizations
    rng = np.random.default_rng(data.draw(st.integers(0, 1000)))
    for _ in range(10):
        left = k
        cand = []
        for _ in range(m - 1):
            divs = [d for d in range(1, left + 1) if left % d == 0]
            d = int(rng.choice(divs))
            cand.append(d)
            left //= d
        cand.append(left)
        assert plan.cost <= replication_cost(sizes, cand) + 1e-6


def test_paper_closed_form_example():
    # §2.2: equal relation sizes, k=27 -> all shares = 3 = cuberoot(27)
    shares = closed_form_shares([1000, 1000, 1000], 27)
    np.testing.assert_allclose(shares, [3.0, 3.0, 3.0], rtol=1e-9)
    # §4.1: shares proportional to dimension sizes
    s = closed_form_shares([2000, 1000, 500], 64)
    assert s[0] > s[1] > s[2]
    np.testing.assert_allclose(s[0] / s[1], 2.0, rtol=1e-9)


@settings(**SETTINGS)
@given(st.data())
def test_weighted_histogram_exact_across_precision_boundaries(data):
    """kernel (interpret) == ref == seed numpy oracle, with weights drawn
    around the 2^24 float-exactness and 2^31 int32 boundaries.

    Runs in whichever accumulation mode the process is in: int32 weights
    always; int64 weights (magnitudes past 2^31) additionally under the CI
    x64 job.  Totals are kept below the weight dtype's wrap point so the
    int64-accumulating seed oracle is comparable; wrap parity itself is
    covered in test_kernels.py.
    """
    x64 = bool(jax.config.jax_enable_x64)
    n = data.draw(st.integers(1, 64))
    tl = data.draw(st.integers(1, 6))
    vocab = data.draw(st.sampled_from([33, 64, 100, 512]))
    # magnitudes straddling each boundary; caps keep Σ w·l·n < 2^31 / 2^63
    if x64 and data.draw(st.booleans()):
        wdtype, hi = jnp.int64, (1 << 52) // (n * tl)
    else:
        wdtype, hi = jnp.int32, (1 << 30) // (n * tl)
    boundary = data.draw(st.sampled_from(
        [0, 1, (1 << 24) - 1, 1 << 24, (1 << 24) + 1, hi]))
    rng = np.random.default_rng(data.draw(st.integers(0, 10_000)))
    toks = jnp.asarray(rng.integers(1, vocab, (tl, n)), jnp.int32)  # [L, N]
    w = np.minimum(rng.integers(0, max(boundary, 2), (n,)), hi)
    w = jnp.asarray(w).astype(wdtype)
    r = np.asarray(fct_ref.weighted_histogram(toks, w, vocab))
    k = np.asarray(weighted_histogram(toks, w, vocab, backend="pallas",
                                      interpret=True))
    np.testing.assert_array_equal(r, k)
    np.testing.assert_array_equal(
        tokens_histogram(np.asarray(toks).T, np.asarray(w), vocab),
        k.astype(np.int64))


@settings(**SETTINGS)
@given(st.data())
def test_cn_enumeration_total_and_minimal(data):
    n_kw = data.draw(st.integers(1, 3))
    m = data.draw(st.integers(1, 3))
    r_max = data.draw(st.integers(1, m + 1))
    full = (1 << n_kw) - 1
    cns = enumerate_star_cns(n_kw, m, r_max)
    seen = set()
    for cn in cns:
        key = (cn.fact_mask, cn.dim_masks, cn.single_dim)
        assert key not in seen, "duplicate CN"
        seen.add(key)
        assert cn.n_relations() <= r_max
        if cn.single_dim >= 0:
            continue
        union = cn.fact_mask
        for i in cn.included:
            union |= cn.dim_masks[i]
        assert union == full, "CN not total"
        for i in cn.included:  # minimality: each leaf contributes uniquely
            u = cn.fact_mask
            for j in cn.included:
                if j != i:
                    u |= cn.dim_masks[j]
            assert u != full, "removable leaf => non-minimal CN"
