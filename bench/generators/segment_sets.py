"""Keyword sets as ``keyword_sets.py`` makes them, each word's band checked
against the relation the mix names for it.

A set in the mix is ``{"words": [...], "bands": [...], "relations": [...],
"only_there": [...]}``: word ``i``'s share of the rows of
``relations[i]`` (rows holding it / rows) must lie in ``bands[i]``, and a
word with ``only_there[i]`` true must occur in no other relation of the
warehouse (a C_MKTSEGMENT word is CUSTOMER's alone, so every CN of its
set passes through CUSTOMER).  A word outside its band is an error of the
mix, not of the run.  Clients, cycles and warm-up are ``keyword_sets``':
each set alone, then every combination of one set from each client's
cycle sent together.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

from bench.generators.keyword_sets import KeywordSets


def row_share(text: np.ndarray, term: int) -> float:
    """Rows of a relation that hold ``term`` / its rows."""
    return float((text == term).any(axis=1).mean())


class SegmentSets(KeywordSets):
    def __init__(self, mix: dict, wh) -> None:
        term_id = {t: i for i, t in enumerate(wh.terms)}
        self.sets = []
        self.shares: List[Tuple[float, ...]] = []
        self.relations = []
        for s in mix["sets"]:
            kws = tuple(term_id[w] for w in s["words"])
            shares = []
            for w, kw, band, rel, only in zip(s["words"], kws, s["bands"],
                                              s["relations"],
                                              s["only_there"]):
                lo, hi = mix["bands"][band]
                share = row_share(wh.relation_text(rel), kw)
                if not lo <= share < hi:
                    raise ValueError(f"{w!r} is in {share:.4%} of {rel}'s "
                                     f"rows, outside band {band!r}")
                if only:
                    elsewhere = [o for o in wh.relations if o != rel and
                                 row_share(wh.relation_text(o), kw) > 0]
                    if elsewhere:
                        raise ValueError(f"{w!r} must occur in {rel} alone, "
                                         f"and occurs in {elsewhere}")
                shares.append(share)
            if len(set(kws)) != len(kws) or kws in self.sets:
                raise ValueError(f"set {s['words']} repeats a word or a set")
            self.sets.append(kws)
            self.shares.append(tuple(shares))
            self.relations.append(tuple(s["relations"]))
        used = [j for c in mix["client_sets"] for j in c]
        if len(used) != len(set(used)):
            raise ValueError("two clients share a set")
        self.cycles = [[self.sets[j] for j in c] for c in mix["client_sets"]]
        self.clients = len(self.cycles)

    def describe(self) -> dict:
        return {"sets": [list(s) for s in self.sets],
                "relations": [list(r) for r in self.relations],
                "row_shares": [[round(x, 6) for x in s]
                               for s in self.shares]}


def make(mix: dict, wh, rng: np.random.Generator) -> SegmentSets:
    """``rng`` is unused: the words are the mix's, the data is the seed's."""
    return SegmentSets(mix, wh)
