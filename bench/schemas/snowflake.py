"""A snowflake: the star of ``star.py`` (LINEITEM to PART, SUPPLIER and
ORDERS) with sub-dimensions one hop behind a dimension (TPC-H's CUSTOMER
behind ORDERS by O_CUSTKEY).

The configuration is ``tpch-sf1``'s with a ``subs`` list; its four star
relations are generated exactly as ``tpch-sf1``'s (``bench/reference/data``,
same seed, same streams).  Each sub-dimension adds, from streams of its own:

* a comment: a TPC-H text string of ``comment_chars`` from a pool of
  ``sub_pool_chars`` characters over the same grammar, in
  ``(max + 1) // 3`` token columns;
* one more token column holding one of ``segment_words`` (C_MKTSEGMENT,
  clause 4.2.2.13), uniform over the rows; these words are new terms;
* the parent's foreign key: uniform over the sub-dimension's keys that are
  not a multiple of ``parent_keys.not_multiple_of`` (O_CUSTKEY's rule,
  clause 4.2.3: a third of the customers have no orders).

Data and reference are the benchmark's own (``bench/reference``); only
``to_program`` touches the program."""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

from bench.reference import data
from bench.reference.snowflake_oracle import Reference  # noqa: F401
from bench.reference.text import Pool, load_grammar


@dataclasses.dataclass
class Sub:
    name: str
    key: str
    parent: str
    text: np.ndarray         # int32 [rows, width]; the key of row r is r
    parent_keys: np.ndarray  # int32 [parent rows]: the parent's foreign key


@dataclasses.dataclass
class Snowflake:
    star: data.Warehouse
    subs: List[Sub]
    vocab: int
    terms: List[str]

    def relation_text(self, name: str) -> np.ndarray:
        if name == self.star.fact_name:
            return self.star.fact_text
        for rel in [*self.star.dims, *self.subs]:
            if rel.name == name:
                return rel.text
        raise KeyError(name)

    @property
    def relations(self) -> List[str]:
        return ([self.star.fact_name] + [d.name for d in self.star.dims]
                + [s.name for s in self.subs])


def generate(cfg: dict, seed: int) -> Snowflake:
    star = data.generate(cfg, seed)
    g = load_grammar()
    n_star = 2 + 2 * (1 + len(cfg["dims"]))     # the streams star.py used
    rngs = data.streams(seed, n_star + 1 + 3 * len(cfg["subs"]))[n_star:]
    pool = Pool(g, rngs[0], cfg["sub_pool_chars"])
    terms = list(g.terms)
    subs = []
    for j, s in enumerate(cfg["subs"]):
        lo, hi = s["comment_chars"]
        text = pool.comments(rngs[1 + 3 * j], s["rows"], lo, hi, g.width(hi))
        first = len(terms)
        terms.extend(s["segment_words"])
        seg = rngs[2 + 3 * j].integers(0, len(s["segment_words"]),
                                       size=s["rows"])
        text = np.concatenate(
            [text, (first + seg).astype(np.int32)[:, None]], axis=1)
        parent, = [d for d in cfg["dims"] if d["name"] == s["parent"]]
        keys = np.arange(s["rows"])
        keys = keys[keys % s["parent_keys"]["not_multiple_of"] != 0]
        fk = keys[rngs[3 + 3 * j].integers(0, keys.size, size=parent["rows"])]
        subs.append(Sub(s["name"], s["key"], s["parent"],
                        np.ascontiguousarray(text, np.int32),
                        fk.astype(np.int32)))
    return Snowflake(star, subs, len(terms), terms)


def to_program(wh: Snowflake):
    """The generated warehouse as the program's snowflake StarSchema (same
    arrays): each sub-dimension's parent gains its foreign-key column."""
    from repro.data.schema import JoinEdge, Relation, StarSchema, SubEdge
    star = wh.star
    fks = {s.parent: s for s in wh.subs}
    dims, edges = [], []
    for d in star.dims:
        n = d.text.shape[0]
        keys = {d.key: np.arange(n, dtype=np.int32)}
        domains = {d.key: n}
        if d.name in fks:
            s = fks[d.name]
            keys[s.key] = s.parent_keys
            domains[s.key] = s.text.shape[0]
        dims.append(Relation(d.name, keys=keys, key_domains=domains,
                             text=d.text))
        edges.append(JoinEdge(d.name, d.key, d.key))
    sub_edges = []
    for s in wh.subs:
        n = s.text.shape[0]
        dims.append(Relation(s.name, keys={s.key: np.arange(n, dtype=np.int32)},
                             key_domains={s.key: n}, text=s.text))
        sub_edges.append(SubEdge(s.name, s.parent, s.key, s.key))
    fact = Relation(star.fact_name, keys=dict(star.fact_keys),
                    key_domains={d.key: d.text.shape[0] for d in star.dims},
                    text=star.fact_text)
    return StarSchema(fact=fact, dims=dims, edges=edges, vocab_size=wh.vocab,
                      sub_edges=sub_edges)
