"""A star: one fact joined to dimensions by foreign keys (TPC-H LINEITEM to
PART, SUPPLIER and ORDERS).  Data and reference are the benchmark's own
(``bench/reference``); only ``to_program`` touches the program."""
from __future__ import annotations

import numpy as np

from bench.reference.data import Warehouse, generate  # noqa: F401
from bench.reference.oracle import Reference  # noqa: F401


def to_program(wh: Warehouse):
    """The generated warehouse as the program's StarSchema (same arrays)."""
    from repro.data.schema import JoinEdge, Relation, StarSchema
    dims, edges = [], []
    for d in wh.dims:
        n = d.text.shape[0]
        dims.append(Relation(d.name, keys={d.key: np.arange(n, dtype=np.int32)},
                             key_domains={d.key: n}, text=d.text))
        edges.append(JoinEdge(d.name, d.key, d.key))
    fact = Relation(wh.fact_name, keys=dict(wh.fact_keys),
                    key_domains={d.key: d.text.shape[0] for d in wh.dims},
                    text=wh.fact_text)
    return StarSchema(fact=fact, dims=dims, edges=edges, vocab_size=wh.vocab)
