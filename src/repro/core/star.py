"""Single-machine FCT baselines.

``fct_bruteforce``  — materializes every MTJNT and counts terms (Def. 6 /
                      Eq. 1–3 taken literally).  Exponential; tests only.
``fct_star``        — the star method of Tao & Yu [12] (the paper's §3
                      starting point): join-free frequency computation via
                      num-arrays and volumes, passed up and down a CN's
                      join tree on a snowflake.  This is the correctness oracle
                      for the distributed engine and the "single machine"
                      baseline of the paper's §6.1 comparison.
Both return an int64 frequency vector over the vocabulary (query keywords and
PAD included — callers mask before top-k, matching Def. 6's "not in q").
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from repro.core.candidate_network import (StarCN, TupleSets, enumerate_cns,
                                          prune_empty_cns)
from repro.data.schema import PAD_ID, StarSchema, tokens_histogram


def _row_histogram(text_rows: np.ndarray, vocab: int) -> np.ndarray:
    return tokens_histogram(text_rows, np.ones(text_rows.shape[0], np.int64), vocab)


def schema_cns(schema: StarSchema, n_keywords: int, r_max: int) -> List[StarCN]:
    """Every CN of the schema's tree (a star's list for a star)."""
    return enumerate_cns(n_keywords, schema.parents, r_max)


class _Tree:
    """One CN's join tree over its tuple sets: the root (-1 for the fact,
    else a dimension), each node's rows, children, and the foreign keys
    that join a node's rows to a child's key."""

    def __init__(self, schema: StarSchema, fact_idx, dim_idx) -> None:
        self.schema = schema
        par = schema.parents
        self.rows: Dict[int, np.ndarray] = dict(dim_idx)
        if fact_idx is not None:
            self.root = -1
            self.rows[-1] = fact_idx
        else:
            self.root, = [i for i in dim_idx if par[i] not in dim_idx]
        self.kids = {v: [c for c in sorted(dim_idx)
                         if c != self.root and par[c] == v]
                     for v in self.rows}

    def text(self, v: int) -> np.ndarray:
        rel = self.schema.fact if v < 0 else self.schema.dims[v]
        return rel.text[self.rows[v]]

    def fk(self, v: int, c: int) -> np.ndarray:
        """Node ``v``'s rows' foreign key into child ``c``."""
        col = (self.schema.fact_keys(c) if v < 0
               else self.schema.parent_fk(c))
        return col[self.rows[v]]

    def pk(self, c: int) -> np.ndarray:
        return self.schema.dim_keys(c)[self.rows[c]]

    def weights(self, dtype) -> Dict[int, np.ndarray]:
        """Per node, each tuple-set row's number of joined trees (its
        volume): an upward pass of num-arrays (rows per key, each row
        weighted by its own subtree's completions), then a downward pass
        of the completions outside each subtree, scatter-added into the
        child's key domain and gathered at its rows."""
        below: Dict[int, np.ndarray] = {}
        num: Dict[int, np.ndarray] = {}
        probe: Dict[tuple, np.ndarray] = {}

        def up(v: int) -> None:
            b = np.ones(len(self.rows[v]), dtype)
            for c in self.kids[v]:
                up(c)
                dom = self.schema.key_domain(c)
                num[c] = _scatter(self.pk(c), below[c], dom, dtype)
                probe[(v, c)] = num[c][self.fk(v, c)]
                b = b * probe[(v, c)]
            below[v] = b

        out = {}

        def down(v: int, above: np.ndarray) -> None:
            out[v] = above * below[v]
            for c in self.kids[v]:
                rest = above.copy()
                for c2 in self.kids[v]:
                    if c2 != c:
                        rest = rest * probe[(v, c2)]
                by_key = _scatter(self.fk(v, c), rest,
                                  self.schema.key_domain(c), dtype)
                down(c, by_key[self.pk(c)])

        up(self.root)
        down(self.root, np.ones(len(self.rows[self.root]), dtype))
        return out

    def results(self, v: int, r: int):
        """Bruteforce: every joined tree below node ``v`` at row ``r``, as
        a list of (node, row) pairs."""
        trees = [[(v, r)]]
        for c in self.kids[v]:
            key = int((self.schema.fact_keys(c) if v < 0
                       else self.schema.parent_fk(c))[r])
            pk = self.schema.dim_keys(c)
            opts = [t for s in self.rows[c] if int(pk[s]) == key
                    for t in self.results(c, int(s))]
            trees = [a + b for a in trees for b in opts]
        return trees


def _scatter(keys: np.ndarray, w: np.ndarray, dom: int, dtype) -> np.ndarray:
    out = np.zeros((dom,), dtype)
    np.add.at(out, keys, w)
    return out


def fct_bruteforce(schema: StarSchema, keywords: Sequence[int],
                   r_max: int) -> np.ndarray:
    """Materialize all MTJNTs of all CNs; count term occurrences."""
    ts = TupleSets.build(schema, keywords)
    cns = prune_empty_cns(schema_cns(schema, len(keywords), r_max), ts)
    freq = np.zeros((schema.vocab_size,), np.int64)
    for cn in cns:
        freq += _bruteforce_cn(schema, ts, cn)
    freq[PAD_ID] = 0
    return freq


def _bruteforce_cn(schema: StarSchema, ts: TupleSets, cn: StarCN) -> np.ndarray:
    vocab = schema.vocab_size
    freq = np.zeros((vocab,), np.int64)
    tree = _Tree(schema, *ts.cn_rows(cn))
    hist = {}
    for r in tree.rows[tree.root]:
        for result in tree.results(tree.root, int(r)):
            for v, row in result:
                if (v, row) not in hist:
                    rel = schema.fact if v < 0 else schema.dims[v]
                    hist[(v, row)] = _row_histogram(rel.text[row:row + 1],
                                                    vocab)
                freq += hist[(v, row)]
    return freq


def fct_star(schema: StarSchema, keywords: Sequence[int],
             r_max: int) -> np.ndarray:
    """Star method: freq(w) = Σ_CN Σ_tuples count(text, w) · vol(tuple)."""
    ts = TupleSets.build(schema, keywords)
    cns = prune_empty_cns(schema_cns(schema, len(keywords), r_max), ts)
    freq = np.zeros((schema.vocab_size,), np.int64)
    for cn in cns:
        freq += star_cn_frequencies(schema, ts, cn)
    freq[PAD_ID] = 0
    return freq


def star_cn_frequencies(schema: StarSchema, ts: TupleSets,
                        cn: StarCN) -> np.ndarray:
    """Join-free per-CN frequencies (Eq. 2 via num-arrays and volumes), by
    message passing over the CN's join tree (a star's is one level)."""
    vocab = schema.vocab_size
    tree = _Tree(schema, *ts.cn_rows(cn))
    freq = np.zeros((vocab,), np.int64)
    for v, w in tree.weights(np.int64).items():
        freq += tokens_histogram(tree.text(v), w, vocab)
    return freq


def cn_volume_mass(schema: StarSchema, ts: TupleSets, cn: StarCN) -> float:
    """Total volume-weighted token mass of a CN: Σ_{w != PAD} freq_CN(w).

    The same num-array/volume pass as :func:`star_cn_frequencies`, collapsed
    over the vocab axis — O(rows·(m+L)) with no histogram.  Every per-term
    frequency is nonnegative, so the mass upper-bounds ``max_w freq_CN(w)``
    and is zero iff the CN contributes nothing to any (non-PAD) term; the
    runtime uses it as the cross-CN-group threshold-pruning bound (the
    bounding trick of "Computing n-Gram Statistics in MapReduce").  float64
    on purpose: a bound needs monotonicity, not bit-exactness — except at
    zero, where products of nonnegative integers are exactly 0.0 iff a
    factor is zero.
    """
    tree = _Tree(schema, *ts.cn_rows(cn))
    mass = 0.0
    for v, w in tree.weights(np.float64).items():
        tokens = (tree.text(v) != PAD_ID).sum(axis=1)
        mass += float(w @ tokens.astype(np.float64))
    return mass


def topk_terms(freq: np.ndarray, keywords: Sequence[int], k: int,
               stop_mask: np.ndarray | None = None):
    """Def. 6: top-k terms by frequency, excluding q (and stopwords/PAD)."""
    f = freq.copy()
    f[PAD_ID] = 0
    for kw in keywords:
        f[kw] = 0
    if stop_mask is not None:
        f[stop_mask] = 0
    order = np.argsort(-f, kind="stable")[:k]
    return order, f[order]
