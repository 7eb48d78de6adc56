"""Seeded TPC-H star warehouse of the benchmark (independent of the program).

A configuration file (``bench/configs/<name>.json``) fixes the shapes: row
counts, each relation's comment length in characters (TPC-H clause 4.2.3),
the size of the text pool, and how LINEITEM's foreign keys are drawn.
``generate(config, seed)`` makes the data from the seed alone, in bulk with
numpy:

* comments: TPC-H text strings over the specification's grammar
  (``bench/reference/text.py``), one pool shared by every relation;
* ``customer_remarks`` rows of a dimension (TPC-H's S_COMMENT rule, 5 per
  10,000 suppliers) hold "Customer" and later "Complaints", and as many
  others "Customer" and later "Recommends";
* fact foreign keys: uniform over each dimension's keys, or Zipf(z) over
  ranks mapped to keys by a permutation drawn from the seed.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, List

import numpy as np

from bench.reference.text import PAD_ID, Grammar, Pool, load_grammar

__all__ = ["PAD_ID", "Dim", "Warehouse", "load_config", "generate"]

CONFIG_DIR = Path(__file__).resolve().parents[1] / "configs"
_CHUNK = 1 << 22          # keys drawn per inverse-CDF step (bounds memory)


@dataclasses.dataclass
class Dim:
    name: str
    key: str
    text: np.ndarray      # int32 [rows, width]; the key of row r is r


@dataclasses.dataclass
class Warehouse:
    fact_name: str
    fact_text: np.ndarray               # int32 [rows, width]
    fact_keys: Dict[str, np.ndarray]    # key name -> int32 [rows]
    dims: List[Dim]
    vocab: int
    terms: List[str]                    # term id -> word


def load_config(name: str) -> dict:
    return json.loads((CONFIG_DIR / f"{name}.json").read_text())


def streams(seed: int, n: int) -> List[np.random.Generator]:
    """``n`` independent generators from one whole-number seed."""
    if seed < 0:
        raise ValueError(f"seed must be a whole number >= 0, got {seed}")
    return [np.random.default_rng(s)
            for s in np.random.SeedSequence(seed).spawn(n)]


def _zipf_cdf(n: int, exponent: float) -> np.ndarray:
    p = np.arange(1, n + 1, dtype=np.float64) ** -exponent
    cdf = np.cumsum(p)
    return cdf / cdf[-1]


def _fact_keys(rng: np.random.Generator, rows: int, domain: int,
               spec: dict) -> np.ndarray:
    if spec["kind"] == "uniform":
        return rng.integers(0, domain, size=rows, dtype=np.int64).astype(
            np.int32)
    if spec["kind"] == "zipf":
        perm = rng.permutation(domain).astype(np.int32)
        cdf = _zipf_cdf(domain, spec["z"])
        out = np.empty(rows, np.int32)
        for lo in range(0, rows, _CHUNK):
            hi = min(rows, lo + _CHUNK)
            out[lo:hi] = np.searchsorted(cdf, rng.random(hi - lo),
                                         side="right")
        return perm[np.minimum(out, domain - 1, out=out)]
    raise ValueError(f"unknown fact key distribution {spec['kind']!r}")


def _customer_remarks(rng: np.random.Generator, text: np.ndarray,
                      g: Grammar, per_kind: int) -> None:
    """"Customer" then a remark word, at random positions of distinct rows
    holding two words or more (the word they land on is replaced)."""
    spec = g.spec["supplier_comments"]
    words = (text != PAD_ID).sum(axis=1)
    kinds = spec["second"]
    rows = rng.choice(np.nonzero(words >= 2)[0], size=per_kind * len(kinds),
                      replace=False)
    for j, r in enumerate(rows):
        k = int(words[r])
        p1 = int(rng.integers(0, k - 1))
        p2 = int(rng.integers(p1 + 1, k))
        text[r, p1] = g.term_id[spec["first"]]
        text[r, p2] = g.term_id[kinds[j // per_kind]]


def comment_width(g: Grammar, rel: dict) -> int:
    return g.width(rel["comment_chars"][1])


def generate(cfg: dict, seed: int) -> Warehouse:
    g = load_grammar()
    rels = [cfg["fact"], *cfg["dims"]]
    rngs = streams(seed, 2 + 2 * len(rels))
    pool = Pool(g, rngs[0], cfg["text_pool_chars"])
    texts = []
    for i, rel in enumerate(rels):
        lo, hi = rel["comment_chars"]
        texts.append(pool.comments(rngs[2 + 2 * i], rel["rows"], lo, hi,
                                   comment_width(g, rel)))
        if rel.get("customer_remarks"):
            _customer_remarks(rngs[3 + 2 * i], texts[-1], g,
                              rel["customer_remarks"])
    del pool
    dims = [Dim(d["name"], d["key"], t) for d, t in zip(cfg["dims"],
                                                         texts[1:])]
    fact_keys = {d["key"]: _fact_keys(rngs[1], cfg["fact"]["rows"],
                                      d["rows"], cfg["fact_keys"])
                 for d in cfg["dims"]}
    return Warehouse(cfg["fact"]["name"], texts[0], fact_keys, dims,
                     g.vocab, g.terms)
