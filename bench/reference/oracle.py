"""Plain host reference of FCT answers (paper Def. 6), written from the
semantics alone.

A keyword query over a star (one fact, ``m`` dimensions joined to it by
foreign keys) is answered over its candidate networks (CNs):

* tuple sets: the rows of a relation whose set of contained query keywords
  is EXACTLY a given subset (DISCOVER semantics), so the joined results of
  different CNs are disjoint and their term counts add;
* a CN is one relation alone holding every keyword, or the fact with an
  exact subset (possibly empty) joined to a non-empty set of dimensions,
  each with a non-empty exact subset, at most ``r_max`` relations, the
  subsets covering the query (total), and no dimension removable
  (minimal; with one dimension, that dimension must not hold every keyword,
  or the fact would be removable);
* a CN's term counts: every joined tuple tree counts each term of each of
  its rows once.  Joined trees through fact row ``t`` number
  ``vol(t) = prod_i num_i(key_i(t))``, ``num_i(a)`` being the tuple-set
  rows of dimension ``i`` with key ``a``; dimension row ``r`` with key ``a``
  appears in ``sum_{t: key_i(t)=a} prod_{j!=i} num_j(key_j(t))`` trees;
* the answer: the histogram over every term (PAD zeroed) summed over CNs,
  and its top k terms with the keywords and PAD excluded, the higher count
  first and, on a tie, the lower term id.

Counts are exact integers.  ``round_to`` is the control: each CN's
histogram is held in a narrower float type (``"bfloat16"``), which breaks
exactness once a count passes what that type holds exactly.
"""
from __future__ import annotations

import itertools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench.reference.data import PAD_ID, Warehouse

_EXACT = float(1 << 53)     # float64 bincount sums are exact below this


class Reference:
    """Answers over one warehouse; per-keyword row masks are cached."""

    def __init__(self, wh: Warehouse) -> None:
        self.wh = wh
        self._has: Dict[Tuple[int, int], np.ndarray] = {}

    def _contains(self, rel: int, kw: int) -> np.ndarray:
        """bool [rows]: does each row of relation ``rel`` (-1 = fact) hold
        keyword ``kw``."""
        got = self._has.get((rel, kw))
        if got is None:
            text = self.wh.fact_text if rel < 0 else self.wh.dims[rel].text
            got = self._has[(rel, kw)] = (text == kw).any(axis=1)
        return got

    def masks(self, rel: int, keywords: Sequence[int]) -> np.ndarray:
        """int64 [rows]: bit b set iff the row holds ``keywords[b]``."""
        rows = (self.wh.fact_text if rel < 0 else self.wh.dims[rel].text)
        out = np.zeros(rows.shape[0], np.int64)
        for b, kw in enumerate(keywords):
            out |= self._contains(rel, kw).astype(np.int64) << b
        return out

    def histogram(self, keywords: Sequence[int], r_max: int,
                  round_to: Optional[str] = None) -> np.ndarray:
        kws = tuple(int(k) for k in keywords)
        fact_m = self.masks(-1, kws)
        dim_m = [self.masks(i, kws) for i in range(len(self.wh.dims))]
        freq = np.zeros(self.wh.vocab, np.int64)
        for cn in star_cns(len(kws), len(self.wh.dims), r_max):
            part = self._cn_counts(cn, fact_m, dim_m)
            if part is None:
                continue
            if round_to is not None:
                part = _round(part, round_to)
            freq += part
        freq[PAD_ID] = 0
        return freq

    def _cn_counts(self, cn, fact_m, dim_m) -> Optional[np.ndarray]:
        fact_mask, leaves = cn
        wh = self.wh
        if fact_mask is None:                       # one dimension alone
            (i, mask), = leaves.items()
            rows = np.nonzero(dim_m[i] == mask)[0]
            if not rows.size:
                return None
            return _hist(wh.dims[i].text[rows], np.ones(rows.size), wh.vocab)
        facts = np.nonzero(fact_m == fact_mask)[0]
        if not facts.size:
            return None
        if not leaves:                              # the fact alone
            return _hist(wh.fact_text[facts], np.ones(facts.size), wh.vocab)
        nums, fkeys, trows = {}, {}, {}
        for i, mask in leaves.items():
            rows = np.nonzero(dim_m[i] == mask)[0]
            if not rows.size:
                return None
            # the key of dimension row r is r
            nums[i] = np.bincount(rows, minlength=wh.dims[i].text.shape[0])
            fkeys[i] = wh.fact_keys[wh.dims[i].key][facts]
            trows[i] = rows
        per = {i: nums[i][fkeys[i]].astype(np.float64) for i in leaves}
        vol = np.ones(facts.size)
        for v in per.values():
            vol *= v
        live = vol > 0
        counts = _hist(wh.fact_text[facts[live]], vol[live], wh.vocab)
        for i in leaves:
            others = np.ones(facts.size)
            for j in leaves:
                if j != i:
                    others *= per[j]
            by_key = np.bincount(fkeys[i], weights=others,
                                 minlength=wh.dims[i].text.shape[0])
            w = by_key[trows[i]]
            live = w > 0
            counts += _hist(wh.dims[i].text[trows[i][live]], w[live],
                            wh.vocab)
        return counts

    def cn_work(self, keywords: Sequence[int], r_max: int) -> dict:
        """What the device histograms for this query: every joined CN with
        no empty tuple set reads each of its relations' tuple-set rows once
        (the row's token ids and one weight, 4 bytes each) and writes one
        vocab-wide histogram per relation (a relation alone is counted on
        the host)."""
        kws = tuple(int(k) for k in keywords)
        fact_m = self.masks(-1, kws)
        dim_m = [self.masks(i, kws) for i in range(len(self.wh.dims))]
        width = [d.text.shape[1] for d in self.wh.dims]
        rows = relations = nbytes = 0
        for fact_mask, leaves in star_cns(len(kws), len(self.wh.dims), r_max):
            if fact_mask is None or not leaves:
                continue
            sizes = [(int(np.count_nonzero(fact_m == fact_mask)),
                      self.wh.fact_text.shape[1])] + [
                (int(np.count_nonzero(dim_m[i] == m)), width[i])
                for i, m in leaves.items()]
            if min(n for n, _ in sizes) > 0:
                rows += sum(n for n, _ in sizes)
                relations += len(sizes)
                nbytes += sum(n * (w + 1) * 4 for n, w in sizes)
                nbytes += len(sizes) * self.wh.vocab * 4
        return {"rows": rows, "relations": relations, "bytes": nbytes}

    def answer(self, keywords: Sequence[int], r_max: int, k: int,
               round_to: Optional[str] = None):
        """(histogram, top-k ids, top-k counts)."""
        freq = self.histogram(keywords, r_max, round_to)
        ids, counts = top_k(freq, keywords, k)
        return freq, ids, counts


def star_cns(n_kw: int, m: int, r_max: int) -> List[tuple]:
    """Every CN as ``(fact_mask, {dim: mask})``; ``fact_mask`` None marks a
    dimension alone."""
    full = (1 << n_kw) - 1
    out: List[tuple] = []
    if r_max >= 1:
        out.append((full, {}))
        out.extend((None, {i: full}) for i in range(m))
    for n_leaves in range(1, min(m, r_max - 1) + 1):
        for leaves in itertools.combinations(range(m), n_leaves):
            for fact_mask in range(full + 1):
                for lm in itertools.product(range(1, full + 1),
                                            repeat=n_leaves):
                    if _total_and_minimal(fact_mask, lm, full):
                        out.append((fact_mask, dict(zip(leaves, lm))))
    return out


def _total_and_minimal(fact_mask: int, leaf_masks: Tuple[int, ...],
                       full: int) -> bool:
    union = fact_mask
    for lm in leaf_masks:
        union |= lm
    if union != full:
        return False
    for i in range(len(leaf_masks)):
        rest = fact_mask
        for j, lm in enumerate(leaf_masks):
            if j != i:
                rest |= lm
        if rest == full:
            return False
    return not (len(leaf_masks) == 1 and leaf_masks[0] == full)


def _hist(text: np.ndarray, weights: np.ndarray, vocab: int) -> np.ndarray:
    """Exact weighted term histogram: bin w = sum of row weights times the
    row's occurrences of w."""
    w = np.repeat(np.asarray(weights, np.float64), text.shape[1])
    if w.sum() >= _EXACT:
        raise OverflowError("weighted count past float64's exact range")
    h = np.bincount(text.reshape(-1), weights=w, minlength=vocab)[:vocab]
    return np.rint(h).astype(np.int64)


def _round(counts: np.ndarray, dtype: str) -> np.ndarray:
    import ml_dtypes
    narrow = counts.astype(np.float64).astype(getattr(ml_dtypes, dtype))
    return narrow.astype(np.float64).astype(np.int64)


def top_k(freq: np.ndarray, keywords: Sequence[int], k: int):
    f = freq.copy()
    f[PAD_ID] = 0
    f[list(keywords)] = 0
    order = np.lexsort((np.arange(f.size), -f))[:k]
    return order, f[order]
