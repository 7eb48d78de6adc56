"""Schema graph, tuple sets and candidate-network enumeration (§3).

Tuple-set semantics follow DISCOVER [17] as used by the paper's example:
``R^K`` is the set of tuples of R whose contained *query*-keyword set is
EXACTLY K.  This makes MTJNT(CN_i) ∩ MTJNT(CN_j) = ∅ (the paper's Eq. 1
precondition) — a result instance determines its CN uniquely from the tree
shape plus each tuple's exact keyword subset — so per-CN frequencies sum.

For a star schema (dimensions connect only through the fact), a candidate
network is a leaf subset L ⊆ dims plus an exact keyword bitmask per node in
{fact} ∪ L.  Validity (Total) and Minimality (Def. 3):
  * union of all masks == full query mask                     (total)
  * every leaf mask ∉ union(other masks)  — i.e. dropping any leaf loses a
    keyword (a leaf with ∅ is a free leaf ⇒ removable ⇒ non-minimal)
  * |L| == 0: fact alone must carry the full mask
  * |L| == 1: the fact is removable too (removal leaves one node), so the
    leaf mask must not be full; and the leaf is removable unless the fact
    mask misses some of its keywords.
Masks may OVERLAP (fact^{k1,k2} ⋈ D^{k2,k3} is a valid CN) — exact-subset
labels keep the result sets disjoint regardless.

A snowflake (``StarSchema`` with sub-dimensions, one level deep) has tree
CNs: a connected subtree of at most ``r_max`` relations with an exact mask
on each node, the union total, inner nodes free to hold ∅, and every leaf
holding a keyword no other node holds (a leaf without one is removable,
so the tree is not minimal).  A CN without the fact is rooted at its
highest dimension: a parent with some of its sub-dimensions, or one
relation alone.  The star rules above are this rule on a star.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import List, Sequence, Tuple

import numpy as np

from repro.data.schema import StarSchema, keyword_mask


@dataclasses.dataclass(frozen=True)
class StarCN:
    """A candidate network: exact keyword bitmask per node.

    ``fact_mask`` — exact keyword bitmask required of fact tuples, or -1
    when the CN holds no fact;
    ``dim_masks`` — per-dimension bitmask (sub-dimensions included), or
    None if the dim is excluded;
    ``single_dim`` — if >= 0, the CN is that single dimension alone (no join).
    A CN with ``fact_mask == -1`` and ``single_dim < 0`` is a dimension
    joined to some of its sub-dimensions, rooted at that dimension.
    """

    fact_mask: int
    dim_masks: Tuple[object, ...]  # int | None per dimension
    single_dim: int = -1

    @property
    def included(self) -> Tuple[int, ...]:
        return tuple(i for i, m in enumerate(self.dim_masks) if m is not None)

    @property
    def has_fact(self) -> bool:
        return self.fact_mask >= 0

    def n_relations(self) -> int:
        if self.single_dim >= 0:
            return 1
        return int(self.has_fact) + len(self.included)


def enumerate_star_cns(n_keywords: int, m_dims: int, r_max: int) -> List[StarCN]:
    """All valid star CNs with ≤ r_max relations."""
    return enumerate_cns(n_keywords, (-1,) * m_dims, r_max)


def enumerate_cns(n_keywords: int, parents: Sequence[int],
                  r_max: int) -> List[StarCN]:
    """All valid tree CNs with ≤ r_max relations over a schema whose
    dimension ``i`` hangs off the fact (``parents[i] == -1``) or off
    dimension ``parents[i]`` (``StarSchema.parents``).

    Order: the fact alone, each dimension alone, the fact-rooted trees by
    node set (``itertools.combinations`` order) and masks, then the
    dimension-rooted trees.  On a star this is the star enumeration's list
    in its order, so a star's plans and their caches do not move."""
    full = (1 << n_keywords) - 1
    n = len(parents)
    cns: List[StarCN] = []
    if r_max >= 1:
        cns.append(StarCN(fact_mask=full, dim_masks=(None,) * n))
        for i in range(n):
            cns.append(StarCN(fact_mask=-1, dim_masks=(None,) * n,
                              single_dim=i))
    # fact-rooted trees: a node set closed under "parent is in the set"
    for nodes in _subsets(range(n)):
        if not nodes or 1 + len(nodes) > r_max:
            continue
        if any(parents[i] >= 0 and parents[i] not in nodes for i in nodes):
            continue
        fact_leaf = sum(parents[i] < 0 for i in nodes) == 1
        inner = {parents[i] for i in nodes if parents[i] >= 0}
        leaves = [i for i in nodes if i not in inner]
        for fact_mask in range(full + 1):
            for masks in itertools.product(
                    *[range(full + 1) if i in inner else range(1, full + 1)
                      for i in nodes]):
                node_masks = dict(zip(nodes, masks))
                if _total_and_minimal(
                        [fact_mask] + list(masks),
                        ([fact_mask] if fact_leaf else [])
                        + [node_masks[i] for i in leaves], full):
                    cns.append(StarCN(fact_mask=fact_mask, dim_masks=tuple(
                        node_masks.get(i) for i in range(n))))
    # dimension-rooted trees: a dimension and some of its sub-dimensions
    for root in range(n):
        kids = [c for c in range(n) if parents[c] == root]
        for sub in _subsets(kids):
            if not sub or 1 + len(sub) > r_max:
                continue
            for masks in itertools.product(range(full + 1),
                                           *[range(1, full + 1)] * len(sub)):
                root_leaf = len(sub) == 1
                if _total_and_minimal(
                        list(masks),
                        list(masks if root_leaf else masks[1:]), full):
                    node_masks = dict(zip((root,) + sub, masks))
                    cns.append(StarCN(fact_mask=-1, dim_masks=tuple(
                        node_masks.get(i) for i in range(n))))
    return cns


def _total_and_minimal(masks: Sequence[int], leaf_masks: Sequence[int],
                       full: int) -> bool:
    """The node masks cover the query, and every leaf holds a keyword that
    no other node holds (dropping it would lose that keyword)."""
    union = 0
    for mk in masks:
        union |= mk
    if union != full:
        return False
    for leaf in leaf_masks:
        rest = 0
        dropped = False
        for mk in masks:
            if mk == leaf and not dropped:
                dropped = True          # drop one node holding this mask
                continue
            rest |= mk
        if leaf & ~rest == 0:
            return False
    return True


def _subsets(items):
    items = list(items)
    out = []
    for r in range(len(items) + 1):
        out.extend(itertools.combinations(items, r))
    return out


@dataclasses.dataclass
class TupleSets:
    """Exact-keyword-subset bitmasks per relation (host-side, one data
    pass); ``dim_kw`` covers every dimension, sub-dimensions included."""

    fact_kw: np.ndarray                 # int64 [fact_rows]
    dim_kw: List[np.ndarray]            # per dim, int64 [rows]
    full: int

    @staticmethod
    def build(schema: StarSchema, keywords: Sequence[int]) -> "TupleSets":
        return TupleSets(
            fact_kw=keyword_mask(schema.fact.text, keywords),
            dim_kw=[keyword_mask(d.text, keywords) for d in schema.dims],
            full=(1 << len(keywords)) - 1,
        )

    def fact_rows(self, mask: int) -> np.ndarray:
        return np.nonzero(self.fact_kw == mask)[0]

    def dim_rows(self, i: int, mask: int) -> np.ndarray:
        return np.nonzero(self.dim_kw[i] == mask)[0]

    def cn_rows(self, cn: StarCN):
        """(fact_row_idx or None, {dim_i: row_idx}) for a CN's tuple sets;
        None for a CN without the fact."""
        if cn.single_dim >= 0:
            return None, {cn.single_dim: self.dim_rows(cn.single_dim, self.full)}
        dims = {i: self.dim_rows(i, cn.dim_masks[i]) for i in cn.included}
        if not cn.has_fact:
            return None, dims
        return self.fact_rows(cn.fact_mask), dims


def prune_empty_cns(cns: List[StarCN], ts: TupleSets) -> List[StarCN]:
    """Drop CNs where some tuple set is empty (no MTJNT can exist)."""
    out = []
    for cn in cns:
        fact_idx, dim_idx = ts.cn_rows(cn)
        if fact_idx is not None and len(fact_idx) == 0:
            continue
        if any(len(v) == 0 for v in dim_idx.values()):
            continue
        out.append(cn)
    return out
