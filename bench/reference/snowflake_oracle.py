"""Plain host reference of FCT answers over a tree of relations (paper
Def. 6 with DISCOVER's candidate networks), written from the semantics
alone.

The warehouse is a tree: the fact at the root, each dimension joined to the
fact by a foreign key of the fact, each sub-dimension joined to its parent
dimension by a foreign key of the parent (CUSTOMER behind ORDERS).  The key
of a dimension's row ``r`` is ``r``.  A star is the tree without
sub-dimensions.

* tuple sets: the rows of a relation whose set of contained query keywords
  is EXACTLY a given subset, so the joined results of different CNs are
  disjoint and their term counts add;
* a CN is a connected subtree of at most ``r_max`` relations with an exact
  subset on each: the subsets cover the query (total), and with two
  relations or more every leaf of the subtree holds a keyword that no other
  relation of it holds (minimal: a leaf without one could be dropped); one
  relation alone holds every keyword;
* a CN's term counts: every joined tuple tree counts each term of each of
  its rows once.  Root the subtree at its relation nearest the fact.  The
  trees below row ``r`` of relation ``v`` number ``below(r) = prod over v's
  children c of num_c(fk_c(r))``, ``num_c(a)`` summing ``below`` over the
  tuple-set rows of ``c`` with key ``a`` (a pass up the tree); the ways to
  complete a tree above and beside ``r`` number ``above(r)``, 1 at the
  root and, for a child row with key ``a``, the sum over the parent's
  tuple-set rows ``r'`` with ``fk_c(r') = a`` of ``above(r')`` times the
  other children's ``num`` at ``r'`` (a pass down); row ``r`` appears in
  ``above(r) * below(r)`` trees;
* the answer: the histogram over every term (PAD zeroed) summed over CNs,
  and its top k terms with the keywords and PAD excluded, the higher count
  first and, on a tie, the lower term id.

Counts are float64 message passing, exact below 2^53 (``oracle._hist``
refuses a weighted count past it).  ``round_to`` is the control: each CN's
histogram is held in a narrower float type (``"bfloat16"``).
"""
from __future__ import annotations

import itertools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench.reference.oracle import PAD_ID, _hist, _round, top_k

__all__ = ["Reference", "tree_cns"]


def _tree(wh) -> Tuple[List[np.ndarray], List[int], List[Optional[np.ndarray]]]:
    """(texts, parents, fks) of a warehouse: relation 0 is the fact, then
    its dimensions, then sub-dimensions (``wh.subs``, if any); ``fks[c]``
    is the column of ``c``'s parent that holds the key of ``c``."""
    star = getattr(wh, "star", wh)
    texts = [star.fact_text] + [d.text for d in star.dims]
    parents = [-1] + [0] * len(star.dims)
    fks: List[Optional[np.ndarray]] = [None] + [star.fact_keys[d.key]
                                                for d in star.dims]
    names = [d.name for d in star.dims]
    for s in getattr(wh, "subs", ()):
        texts.append(s.text)
        parents.append(1 + names.index(s.parent))
        fks.append(s.parent_keys)
    return texts, parents, fks


def tree_cns(n_kw: int, parents: Sequence[int], r_max: int) -> List[tuple]:
    """Every CN as ``(root, {relation: mask})``: connected subtrees of the
    schema tree (``parents[v]`` is -1 at the root) with total, minimal exact
    masks."""
    full = (1 << n_kw) - 1
    n = len(parents)
    out: List[tuple] = []
    for size in range(1, min(n, r_max) + 1):
        for nodes in itertools.combinations(range(n), size):
            tops = [v for v in nodes if parents[v] not in nodes]
            if len(tops) != 1:              # not connected
                continue
            degree = {v: sum(parents[u] == v for u in nodes)
                      + (parents[v] in nodes) for v in nodes}
            leaves = [v for v in nodes if degree[v] <= 1] if size > 1 else []
            for masks in itertools.product(range(full + 1), repeat=size):
                got = dict(zip(nodes, masks))
                union = 0
                for mk in masks:
                    union |= mk
                if union != full:
                    continue
                if all(got[v] & ~_others(got, v) for v in leaves):
                    out.append((tops[0], got))
    return out


def _others(masks: Dict[int, int], v: int) -> int:
    out = 0
    for u, mk in masks.items():
        if u != v:
            out |= mk
    return out


class Reference:
    """Answers over one warehouse; per-(relation, mask) tuple sets cached
    per keyword set."""

    def __init__(self, wh) -> None:
        self.wh = wh
        self.vocab = wh.vocab
        self.texts, self.parents, self.fks = _tree(wh)
        self._has: Dict[Tuple[int, int], np.ndarray] = {}

    def _contains(self, rel: int, kw: int) -> np.ndarray:
        got = self._has.get((rel, kw))
        if got is None:
            got = self._has[(rel, kw)] = (self.texts[rel] == kw).any(axis=1)
        return got

    def masks(self, rel: int, keywords: Sequence[int]) -> np.ndarray:
        """int64 [rows]: bit b set iff the row holds ``keywords[b]``."""
        out = np.zeros(self.texts[rel].shape[0], np.int64)
        for b, kw in enumerate(keywords):
            out |= self._contains(rel, kw).astype(np.int64) << b
        return out

    def _tuple_sets(self, keywords):
        masks = [self.masks(v, keywords) for v in range(len(self.texts))]
        cache: Dict[Tuple[int, int], np.ndarray] = {}

        def rows(v: int, mk: int) -> np.ndarray:
            if (v, mk) not in cache:
                cache[(v, mk)] = np.nonzero(masks[v] == mk)[0]
            return cache[(v, mk)]
        return rows

    def histogram(self, keywords: Sequence[int], r_max: int,
                  round_to: Optional[str] = None) -> np.ndarray:
        kws = tuple(int(k) for k in keywords)
        rows = self._tuple_sets(kws)
        freq = np.zeros(self.vocab, np.int64)
        for root, got in tree_cns(len(kws), self.parents, r_max):
            sets = {v: rows(v, mk) for v, mk in got.items()}
            if any(not s.size for s in sets.values()):
                continue
            part = self._cn_counts(root, sets)
            if round_to is not None:
                part = _round(part, round_to)
            freq += part
        freq[PAD_ID] = 0
        return freq

    def _cn_counts(self, root: int, sets: Dict[int, np.ndarray]) -> np.ndarray:
        kids = {v: [c for c in sets if self.parents[c] == v] for v in sets}
        below: Dict[int, np.ndarray] = {}
        probe: Dict[int, np.ndarray] = {}

        def up(v: int) -> None:
            b = np.ones(sets[v].size)
            for c in kids[v]:
                up(c)
                num = np.bincount(sets[c], weights=below[c],
                                  minlength=self.texts[c].shape[0])
                probe[c] = num[self.fks[c][sets[v]]]
                b = b * probe[c]
            below[v] = b

        counts = np.zeros(self.vocab, np.int64)

        def down(v: int, above: np.ndarray) -> None:
            nonlocal counts
            w = above * below[v]
            live = w > 0
            counts += _hist(self.texts[v][sets[v][live]], w[live], self.vocab)
            for c in kids[v]:
                rest = above.copy()
                for c2 in kids[v]:
                    if c2 != c:
                        rest = rest * probe[c2]
                by_key = np.bincount(self.fks[c][sets[v]], weights=rest,
                                     minlength=self.texts[c].shape[0])
                down(c, by_key[sets[c]])

        up(root)
        down(root, np.ones(sets[root].size))
        return counts

    def cn_work(self, keywords: Sequence[int], r_max: int) -> dict:
        """What the device histograms for this query: every joined CN with
        no empty tuple set reads each of its relations' tuple-set rows once
        (the row's token ids and one weight, 4 bytes each) and writes one
        vocab-wide histogram per relation (a relation alone is counted on
        the host)."""
        kws = tuple(int(k) for k in keywords)
        rows = self._tuple_sets(kws)
        n_rows = relations = nbytes = 0
        for _, got in tree_cns(len(kws), self.parents, r_max):
            if len(got) < 2:
                continue
            sizes = [(rows(v, mk).size, self.texts[v].shape[1])
                     for v, mk in got.items()]
            if min(n for n, _ in sizes) > 0:
                n_rows += sum(n for n, _ in sizes)
                relations += len(sizes)
                nbytes += sum(n * (w + 1) * 4 for n, w in sizes)
                nbytes += len(sizes) * self.vocab * 4
        return {"rows": n_rows, "relations": relations, "bytes": nbytes}

    def answer(self, keywords: Sequence[int], r_max: int, k: int,
               round_to: Optional[str] = None):
        """(histogram, top-k ids, top-k counts)."""
        freq = self.histogram(keywords, r_max, round_to)
        ids, counts = top_k(freq, keywords, k)
        return freq, ids, counts
