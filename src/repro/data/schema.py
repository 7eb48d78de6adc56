"""Relational substrate with dense, static-shape storage.

Hadoop streams variadic records; a TPU wants static shapes.  A Relation is
stored as
  * one int32 key column per join attribute (dense key ids in [0, domain)),
  * an int32 token matrix ``text[rows, text_len]`` (PAD_ID padded) holding the
    tokenized concatenation of all non-key attributes.

A Schema describes a star or a one-level snowflake: one fact relation
joined to ``m`` dimension relations through (fact_col -> dim_col) foreign
keys, and optionally sub-dimensions, each joined to one of those dimensions
through a (parent_col -> child_col) foreign key of the parent.  This mirrors
the paper's experimental setup (LINEITEM fact; PART / SUPPLIER / ORDERS
dimensions; CUSTOMER behind ORDERS for the chain and mix queries).
"""
from __future__ import annotations

import dataclasses
from typing import Mapping, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

PAD_ID = 0  # token id reserved for padding; never counted as a term


@dataclasses.dataclass
class Relation:
    """A relation with dense int key columns and a fixed-width token matrix.

    ``chunks`` records the append history as per-chunk row counts (None =
    one chunk covering every row).  Appends are the ONLY mutation that
    preserves derived state: :meth:`append_rows` returns a NEW Relation
    whose column arrays are fresh concatenations — the old object (and any
    plan/ref holding its arrays) keeps seeing the pre-append snapshot, and
    a prefix of the new arrays is value-identical to the old ones, so
    content-addressed device columns stay valid per chunk.
    """

    name: str
    keys: Mapping[str, np.ndarray]        # col -> int32 [rows]
    key_domains: Mapping[str, int]        # col -> domain size (keys < domain)
    text: np.ndarray                      # int32 [rows, text_len]
    chunks: Optional[Tuple[int, ...]] = None  # append-chunk row counts

    def __post_init__(self) -> None:
        rows = self.text.shape[0]
        for col, arr in self.keys.items():
            assert arr.shape == (rows,), (self.name, col, arr.shape, rows)
            assert arr.dtype == np.int32
        assert self.text.dtype == np.int32
        if self.chunks is not None:
            assert sum(self.chunks) == rows, (self.name, self.chunks, rows)
            assert all(c > 0 for c in self.chunks), (self.name, self.chunks)

    @property
    def rows(self) -> int:
        return int(self.text.shape[0])

    @property
    def text_len(self) -> int:
        return int(self.text.shape[1])

    def take(self, idx: np.ndarray) -> "Relation":
        # a row subset is not chunk-aligned: the copy is a fresh single chunk
        return Relation(
            name=self.name,
            keys={c: np.asarray(a[idx], np.int32) for c, a in self.keys.items()},
            key_domains=dict(self.key_domains),
            text=np.asarray(self.text[idx], np.int32),
        )

    def append_rows(self, keys: Mapping[str, np.ndarray],
                    text: np.ndarray,
                    domain_overrides: Optional[Mapping[str, int]] = None
                    ) -> "Relation":
        """New Relation with ``text.shape[0]`` rows appended as one chunk.

        Validates column set, dtypes, text width and key domains; an empty
        append returns ``self`` unchanged (no new chunk).  The returned
        relation's ``chunks`` grows by one entry; existing chunk boundaries
        never move, so refs built against the old object stay exact.
        ``domain_overrides`` grows named key domains (never shrinks them) —
        a dimension append introduces fresh primary-key values, and
        :meth:`StarSchema.with_appended` mirrors the growth into the fact's
        foreign-key domain to keep the schema invariant.
        """
        n_new = int(text.shape[0])
        if n_new == 0:
            return self
        if set(keys) != set(self.keys):
            raise ValueError(
                f"append to {self.name!r} must provide exactly the key "
                f"columns {sorted(self.keys)}, got {sorted(keys)}")
        if text.shape[1:] != self.text.shape[1:]:
            raise ValueError(
                f"append to {self.name!r}: text width {text.shape[1:]} != "
                f"{self.text.shape[1:]}")
        text = np.ascontiguousarray(text, np.int32)
        new_domains = dict(self.key_domains)
        for col, dom in (domain_overrides or {}).items():
            if dom < new_domains[col]:
                raise ValueError(
                    f"append to {self.name!r}: key domain {col!r} cannot "
                    f"shrink ({new_domains[col]} -> {dom})")
            new_domains[col] = int(dom)
        new_keys = {}
        for col, arr in keys.items():
            arr = np.ascontiguousarray(arr, np.int32)
            if arr.shape != (n_new,):
                raise ValueError(
                    f"append to {self.name!r}: key column {col!r} has shape "
                    f"{arr.shape}, expected ({n_new},)")
            dom = new_domains[col]
            if arr.size and (arr.min() < 0 or arr.max() >= dom):
                raise ValueError(
                    f"append to {self.name!r}: key column {col!r} outside "
                    f"[0, {dom})")
            new_keys[col] = np.concatenate([self.keys[col], arr])
        old_chunks = self.chunks if self.chunks is not None else (self.rows,)
        return Relation(
            name=self.name, keys=new_keys, key_domains=new_domains,
            text=np.concatenate([self.text, text]),
            chunks=old_chunks + (n_new,))


@dataclasses.dataclass(frozen=True)
class JoinEdge:
    """fact.fact_col references dim.dim_col (FK -> PK in the schema graph)."""

    dim_name: str
    fact_col: str
    dim_col: str


@dataclasses.dataclass(frozen=True)
class SubEdge:
    """parent.parent_col references child.child_col: a sub-dimension edge
    (dimension -> dimension, FK -> PK), one level below the fact."""

    child_name: str
    parent_name: str
    parent_col: str
    child_col: str


@dataclasses.dataclass
class StarSchema:
    """One fact relation + m dimensions, and optional sub-dimensions.

    ``dims[:m]`` are the fact's dimensions (``edges[i]`` joins the fact to
    ``dims[i]``); ``dims[m:]`` are sub-dimensions, ``sub_edges[j]`` joining
    ``dims[m + j]`` to its parent, which must be one of ``dims[:m]``.  A
    star has no sub-edges.  The schema graph is a tree (every relation is
    reached from the fact by one path), at most two hops deep.
    """

    fact: Relation
    dims: Sequence[Relation]
    edges: Sequence[JoinEdge]  # edges[i] joins fact to dims[i]
    vocab_size: int
    sub_edges: Sequence[SubEdge] = ()  # sub_edges[j] joins dims[m + j]

    def __post_init__(self) -> None:
        assert len(self.dims) == len(self.edges) + len(self.sub_edges)
        for dim, edge in zip(self.dims, self.edges):
            assert dim.name == edge.dim_name
            d_fact = self.fact.key_domains[edge.fact_col]
            d_dim = dim.key_domains[edge.dim_col]
            assert d_fact == d_dim, (edge, d_fact, d_dim)
        names = [self.fact.name] + [d.name for d in self.dims]
        if len(set(names)) != len(names):
            raise ValueError(f"relation names repeat: {names}")
        top = {d.name: i for i, d in enumerate(self.dims[:self.m])}
        for j, se in enumerate(self.sub_edges):
            child = self.dims[self.m + j]
            if child.name != se.child_name:
                raise ValueError(
                    f"sub_edges[{j}] names child {se.child_name!r} but "
                    f"dims[{self.m + j}] is {child.name!r}")
            if se.parent_name not in top:
                raise ValueError(
                    f"sub-dimension {se.child_name!r} hangs off "
                    f"{se.parent_name!r}, which is not a dimension of the "
                    f"fact: only one level of sub-dimensions is supported, "
                    f"and the schema graph must be a tree")
            parent = self.dims[top[se.parent_name]]
            d_par = parent.key_domains[se.parent_col]
            d_child = child.key_domains[se.child_col]
            if d_par != d_child:
                raise ValueError(f"{se}: parent key domain {d_par} != "
                                 f"child key domain {d_child}")

    @property
    def m(self) -> int:
        """Number of the fact's own dimensions (sub-dimensions excluded)."""
        return len(self.edges)

    @property
    def parents(self) -> Tuple[int, ...]:
        """Per dimension: -1 for the fact's dimensions, the parent's index
        in ``dims`` for a sub-dimension.  A star is all -1."""
        top = {d.name: i for i, d in enumerate(self.dims[:self.m])}
        return (-1,) * self.m + tuple(top[se.parent_name]
                                      for se in self.sub_edges)

    def key_domain(self, i: int) -> int:
        """Domain of dimension ``i``'s key (and of the FK that joins it)."""
        if i < self.m:
            return self.fact.key_domains[self.edges[i].fact_col]
        return self.dims[i].key_domains[self.sub_edges[i - self.m].child_col]

    def fact_keys(self, i: int) -> np.ndarray:
        return self.fact.keys[self.edges[i].fact_col]

    def dim_keys(self, i: int) -> np.ndarray:
        """Dimension ``i``'s own key column."""
        if i < self.m:
            return self.dims[i].keys[self.edges[i].dim_col]
        return self.dims[i].keys[self.sub_edges[i - self.m].child_col]

    def parent_fk(self, i: int) -> np.ndarray:
        """The column of sub-dimension ``i``'s parent that references it."""
        se = self.sub_edges[i - self.m]
        return self.dims[self.parents[i]].keys[se.parent_col]

    def children(self, i: int) -> Tuple[int, ...]:
        """Sub-dimensions of dimension ``i``, in ``dims`` order."""
        return tuple(c for c, p in enumerate(self.parents) if p == i)

    def relation_role(self, name: str) -> Tuple[str, int]:
        """("fact", -1) or ("dim", i) for a relation name (``i >= m`` for a
        sub-dimension); KeyError else."""
        if name == self.fact.name:
            return "fact", -1
        for i, dim in enumerate(self.dims):
            if dim.name == name:
                return "dim", i
        raise KeyError(f"unknown relation {name!r} (fact is "
                       f"{self.fact.name!r}, dims are "
                       f"{[d.name for d in self.dims]})")

    def with_appended(self, name: str, keys: Mapping[str, np.ndarray],
                      text: np.ndarray) -> "StarSchema":
        """New StarSchema with rows appended to one relation as a chunk.

        The receiver is NOT mutated: callers that hold the old object (plans
        in flight, cached tuple sets) keep a consistent pre-append snapshot.
        Unchanged relations are shared by reference.

        A dimension append may introduce primary-key values past the current
        domain (new dim rows ARE new keys); the domain grows to cover them
        and the referencing foreign-key domain — the fact's, or a
        sub-dimension's parent's — grows in lockstep (the schema invariant
        ``d_fk == d_pk``); the referencing relation's column arrays are
        still shared, only its metadata dict is replaced.  Appends must
        reference existing keys through their own foreign keys.
        """
        role, i = self.relation_role(name)
        if role == "fact":
            return dataclasses.replace(
                self, fact=self.fact.append_rows(keys, text))
        if i < self.m:
            pk_col, fk_col = self.edges[i].dim_col, self.edges[i].fact_col
        else:
            se = self.sub_edges[i - self.m]
            pk_col, fk_col = se.child_col, se.parent_col
        dims = list(self.dims)
        pk = np.asarray(keys[pk_col]) if pk_col in keys else None
        new_dom = dims[i].key_domains[pk_col]
        if pk is not None and pk.size:
            new_dom = max(new_dom, int(pk.max()) + 1)
        dims[i] = dims[i].append_rows(
            keys, text, domain_overrides={pk_col: new_dom})
        fact = self.fact
        if i < self.m:
            if new_dom != fact.key_domains[fk_col]:
                fact = dataclasses.replace(
                    fact, key_domains={**fact.key_domains,
                                       fk_col: new_dom})
        else:
            p = self.parents[i]
            if new_dom != dims[p].key_domains[fk_col]:
                dims[p] = dataclasses.replace(
                    dims[p], key_domains={**dims[p].key_domains,
                                          fk_col: new_dom})
        return dataclasses.replace(self, fact=fact, dims=tuple(dims))


def keyword_mask(text: np.ndarray, keywords: Sequence[int]) -> np.ndarray:
    """Bitmask [rows] of which query keywords each row's text contains."""
    rows = text.shape[0]
    mask = np.zeros((rows,), np.int64)
    for bit, kw in enumerate(keywords):
        mask |= (text == kw).any(axis=1).astype(np.int64) << bit
    return mask


def count_token(text: np.ndarray, token: int) -> np.ndarray:
    """Occurrences (with multiplicity) of ``token`` per row."""
    return (text == token).sum(axis=1).astype(np.int64)


def tokens_histogram(text: np.ndarray, weights: np.ndarray, vocab: int) -> np.ndarray:
    """Weighted token histogram: hist[w] = sum_rows weight[row]*count(row, w).

    Host/numpy oracle used by the single-machine star baseline.
    """
    flat = text.reshape(-1)
    w = np.repeat(np.asarray(weights, np.int64), text.shape[1])
    hist = np.bincount(flat, weights=w, minlength=vocab)[:vocab]
    hist[PAD_ID] = 0
    return hist.astype(np.int64)


def as_device_arrays(rel: Relation) -> dict:
    """Pack a relation into jnp arrays (used by the device jobs)."""
    out = {f"key:{c}": jnp.asarray(v) for c, v in rel.keys.items()}
    out["text"] = jnp.asarray(rel.text)
    return out
