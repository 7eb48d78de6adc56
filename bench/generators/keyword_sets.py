"""Keyword sets of words the text itself holds, named in the mix, each word
checked against the selectivity band the mix gives it.

A term's selectivity is its share of the fact's rows (occurrences in the
fact's comments / fact rows, read from the generated data).  The mix names
bands (``bands``: name -> ``[low, high)`` share) and the sets (``sets``:
each ``{"words": [...], "bands": [...]}``, one band per word); a word
outside its band is an error of the mix, not of the run.  The words are
fixed, so every seed asks for the same work over other data.
``client_sets[i]`` lists the sets client ``i`` cycles through; no two
clients share a set, so in-flight requests are never identical.

Warm-up: each set alone, then every combination of one set from each
client's cycle sent together: every batch the gateway can form from the
clients' in-flight requests is among them.
"""
from __future__ import annotations

import itertools
from typing import List, Tuple

import numpy as np

KeywordSet = Tuple[int, ...]


def term_shares(fact_text: np.ndarray, vocab: int) -> np.ndarray:
    """Occurrences of each term in the fact / fact rows (PAD is 0)."""
    share = np.bincount(fact_text.reshape(-1), minlength=vocab)[:vocab]
    share = share.astype(np.float64) / fact_text.shape[0]
    share[0] = 0.0
    return share


class KeywordSets:
    def __init__(self, mix: dict, wh) -> None:
        share = term_shares(wh.fact_text, wh.vocab)
        term_id = {t: i for i, t in enumerate(wh.terms)}
        self.sets: List[KeywordSet] = []
        self.shares: List[Tuple[float, ...]] = []
        for s in mix["sets"]:
            kws = tuple(term_id[w] for w in s["words"])
            for w, kw, band in zip(s["words"], kws, s["bands"]):
                lo, hi = mix["bands"][band]
                if not lo <= share[kw] < hi:
                    raise ValueError(f"{w!r} is in {share[kw]:.4%} of the "
                                     f"fact's rows, outside band {band!r}")
            if len(set(kws)) != len(kws) or kws in self.sets:
                raise ValueError(f"set {s['words']} repeats a word or a set")
            self.sets.append(kws)
            self.shares.append(tuple(float(share[k]) for k in kws))
        used = [j for c in mix["client_sets"] for j in c]
        if len(used) != len(set(used)):
            raise ValueError("two clients share a set")
        self.cycles = [[self.sets[j] for j in c] for c in mix["client_sets"]]
        self.clients = len(self.cycles)

    def warmup(self) -> List[List[KeywordSet]]:
        alone = [[s] for s in self.sets]
        together = [list(c) for c in itertools.product(*self.cycles)]
        return alone + (together if self.clients > 1 else [])

    def client(self, i: int):
        return itertools.cycle(self.cycles[i])

    def describe(self) -> dict:
        return {"sets": [list(s) for s in self.sets],
                "fact_row_shares": [[round(x, 6) for x in s]
                                    for s in self.shares]}


def make(mix: dict, wh, rng: np.random.Generator) -> KeywordSets:
    """``rng`` is unused: the words are the mix's, the data is the seed's."""
    return KeywordSets(mix, wh)
