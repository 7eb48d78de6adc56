"""The benchmark's own data generator and reference, against the program's
host oracle at a small size on the CPU."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.generators import keyword_sets  # noqa: E402
from bench.reference.data import generate, load_config  # noqa: E402
from bench.reference.oracle import Reference, star_cns  # noqa: E402
from bench.reference.text import load_grammar  # noqa: E402
from bench.schemas import star  # noqa: E402


def small_config(name="tpch-sf1", fact=30000):
    """The configuration with fewer rows and a smaller text pool; widths,
    comment lengths and the grammar as configured."""
    cfg = load_config(name)
    cfg["fact"]["rows"] = fact
    for d, n in zip(cfg["dims"], (1200, 200, 3000)):
        d["rows"] = n
    cfg["text_pool_chars"] = 2_000_000
    cfg["dims"][1]["customer_remarks"] = 2
    return cfg


def mix_sets(wh, seed):
    mix = json.loads((ROOT / "bench" / "mixes" / "star_repeat.json")
                     .read_text())
    return keyword_sets.make(mix, wh, np.random.default_rng(seed)).sets


def test_generator_is_deterministic_and_keeps_tpch_widths():
    cfg = small_config()
    a, b = generate(cfg, 2**31 + 17), generate(cfg, 2**31 + 17)
    assert np.array_equal(a.fact_text, b.fact_text)
    assert all(np.array_equal(a.fact_keys[k], b.fact_keys[k])
               for k in a.fact_keys)
    c = generate(cfg, 2**31 + 18)
    assert not np.array_equal(a.fact_text, c.fact_text)
    # (max chars + 1) // 3 columns: L_COMMENT 14, P 7, S 33, O 26
    assert [a.fact_text.shape[1]] + [d.text.shape[1] for d in a.dims] == [
        14, 7, 33, 26]
    g = load_grammar()
    assert a.vocab == g.vocab == 211 and a.terms == g.terms
    supp = a.dims[1].text
    for word in ("Customer", "Complaints", "Recommends"):
        rows = (supp == g.term_id[word]).any(axis=1).sum()
        assert rows == (4 if word == "Customer" else 2)
        assert not (a.fact_text == g.term_id[word]).any()


def test_comments_follow_tpch_lengths_and_weights():
    wh = generate(small_config(fact=60000), 5)
    g = load_grammar()
    words = (wh.fact_text != 0).sum(axis=1)
    # 10..43 characters of words of 2+ letters, broken ends dropped
    assert words.max() <= 14 and 2.0 < words.mean() < 4.0
    share = keyword_sets.term_shares(wh.fact_text, wh.vocab)
    # dbgen's weights: "the" opens every prepositional phrase; adjective
    # "regular" (50) far above "furious" (1); TPC-H Q13's "special" and
    # "requests" are common
    assert share.argmax() == g.term_id["the"]
    assert share[g.term_id["regular"]] > 20 * share[g.term_id["furious"]]
    assert share[g.term_id["special"]] > 0.02
    assert share[g.term_id["requests"]] > 0.04
    # padding stays at the end of a row
    live = wh.fact_text != 0
    assert (live[:, :-1] | ~live[:, 1:]).all()


def test_zipf_keys_are_skewed():
    wh = generate(small_config("tpch-sf1-zipf1", fact=60000), 5)
    counts = np.bincount(wh.fact_keys["partkey"], minlength=1200)
    top = np.sort(counts)[::-1]
    assert top[0] > 20 * np.median(counts)   # z=1: the hottest key dominates


@pytest.mark.parametrize("n_kw", [1, 2, 3])
@pytest.mark.parametrize("r_max", [1, 2, 3, 4])
def test_cn_enumeration_matches_program(n_kw, r_max):
    from repro.core.candidate_network import enumerate_star_cns
    mine = {(f, tuple(sorted(d.items()))) for f, d in star_cns(n_kw, 3,
                                                               r_max)}
    theirs = set()
    for cn in enumerate_star_cns(n_kw, 3, r_max):
        if cn.single_dim >= 0:
            theirs.add((None, ((cn.single_dim, (1 << n_kw) - 1),)))
        else:
            theirs.add((cn.fact_mask, tuple((i, m) for i, m in
                                            enumerate(cn.dim_masks)
                                            if m is not None)))
    assert mine == theirs


@pytest.mark.parametrize("seed", [0, 3, 2**31 + 9])
@pytest.mark.parametrize("config", ["tpch-sf1", "tpch-sf1-zipf1"])
def test_reference_equals_fct_star(seed, config):
    from repro.core.star import fct_star, topk_terms
    wh = generate(small_config(config), seed)
    schema, ref = star.to_program(wh), Reference(wh)
    g = load_grammar()
    sets = mix_sets(wh, seed) + [
        (g.term_id["Customer"], g.term_id["Complaints"]),
        (g.term_id["regular"], g.term_id["foxes"], g.term_id["sleep"])]
    for kws in sets:
        want = fct_star(schema, kws, 4)
        freq, ids, counts = ref.answer(kws, 4, 10)
        assert np.array_equal(freq, want), kws
        w_ids, w_counts = topk_terms(want, kws, 10)
        assert np.array_equal(ids, w_ids) and np.array_equal(counts,
                                                             w_counts)


def test_every_star_set_has_one_full_fact_cn():
    """Every mix set's words lie in all four relations, so its CNs include
    dimension pairs joined through the whole fact (the fact's tuple set of
    rows holding neither keyword); ``cn_work`` counts each live CN's
    tuple-set rows and bytes."""
    cfg = small_config()
    wh = generate(cfg, 11)
    ref = Reference(wh)
    for kws in mix_sets(wh, 11):
        fact_m = ref.masks(-1, kws)
        dim_m = [ref.masks(i, kws) for i in range(3)]
        widths = [d.text.shape[1] for d in wh.dims]
        rows = nbytes = free = 0
        for fact_mask, leaves in star_cns(len(kws), 3, 4):
            if fact_mask is None or not leaves:
                continue
            sizes = [(np.count_nonzero(fact_m == fact_mask), 14)] + [
                (np.count_nonzero(dim_m[i] == m), widths[i])
                for i, m in leaves.items()]
            if min(n for n, _ in sizes) == 0:
                continue
            free += fact_mask == 0
            rows += sum(n for n, _ in sizes)
            nbytes += sum(4 * n * (w + 1) + 4 * wh.vocab for n, w in sizes)
        assert free >= 1, kws
        work = ref.cn_work(kws, 4)
        assert (work["rows"], work["bytes"]) == (rows, nbytes)
