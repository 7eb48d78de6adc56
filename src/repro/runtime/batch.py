"""Shape bucketing and multi-CN stacking for the FCT runtime.

A ``CNPlan``'s device arrays have data-dependent dims: per-device rows ``S``
(tuple-set size / P), send-table capacity ``C`` (max rows any worker ships to
any other) and text width ``L``.  Left alone, every CN of every query lowers
to a fresh XLA program.  Bucketing rounds each of those dims up to a power of
two (``BUCKET_MIN`` floor), so the infinite family of exact shapes collapses
onto a small lattice of *signatures* — the unit of executable caching and of
multi-CN batching.

Padding is semantics-free by construction:
  * extra ``S`` rows are never named by any send-table entry,
  * extra ``C`` slots hold -1, which the device program masks out,
  * extra ``L`` columns hold PAD_ID, which the histogram never counts,
  * a larger key ``domain`` only grows the num-arrays' zero tail.

``stack_group`` then stacks same-signature plans along a leading CN axis
[N, P, ...]; the engine vmaps the per-CN device program over that axis.

Beside the shape lattice, a signature carries the query's
:class:`~repro.core.accum.AccumPolicy` — the device accumulation width and
overflow behavior.  Two plans with equal shapes but different policies lower
to different XLA programs (int32 vs int64 accumulators), so the policy must
be part of the signature for the executable cache and batching to stay
sound.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.accum import INT32_CHECKED, AccumPolicy
from repro.core.plan import CNPlan, RelationRoute, lane_major
from repro.data.schema import PAD_ID

BUCKET_MIN = 8


def x64_flag() -> bool:
    """The ``jax_enable_x64`` predicate every runtime cache key must share:
    executables (engine), device-resident columns (store) and the two-job
    programs key on exactly this, so arrays uploaded under one mode can
    never be served to a program compiled under the other."""
    return bool(jax.config.jax_enable_x64)


def bucket_pow2(n: int, minimum: int = BUCKET_MIN) -> int:
    """Smallest power of two >= max(n, minimum)."""
    n = max(int(n), minimum, 1)
    return 1 << (n - 1).bit_length()


@dataclasses.dataclass(frozen=True)
class RelationSig:
    """Padded dims of one routed relation: [P, text_len, rows] text
    (lane-major, see ``repro.core.plan.lane_major``), [P, P, cap] send
    table, key domain (0 for the fact side).

    ``key_width`` is the fact relation's FULL key-column count (0 for dims):
    the store-path device program takes the full-width stored key matrix
    [P, key_width, rows] plus a per-CN column-index gather, so its shapes —
    and hence the executable-cache key — depend on it.  A hop's parent
    dimension stores its own key and its FKs to its sub-dimensions, so its
    ``key_width`` is their count.

    ``parent`` marks a hop sub-dimension: the slot (index into
    ``PlanSignature.dims``) of the dimension it hangs off, whose stored key
    row ``parent_key`` holds the foreign key to it; -1 for a dimension of
    the root."""

    rows: int
    cap: int
    text_len: int
    domain: int = 0
    key_width: int = 0
    parent: int = -1
    parent_key: int = 0


@dataclasses.dataclass(frozen=True)
class PlanSignature:
    """Shape-bucket signature of a CNPlan — the executable-cache key's
    structural part.  Two plans with equal signatures lower to the same XLA
    program and may be stacked into one batched dispatch.

    ``accum`` is the device accumulation policy (int32-checked vs
    int64-exact): it changes the dtype of every volume/histogram in the
    program body, so it is as much a part of the program's identity as the
    shapes are.

    ``k_bucket`` identifies the top-k finalize family (``fct_topk``): the
    pow-2-bucketed candidate count each device keeps, 0 for histogram
    programs.  Bucketing k the same way as shapes means nearby ``top_k``
    requests (k=10 and k=12, say) reuse one executable."""

    n_devices: int
    vocab: int
    fact: RelationSig
    dims: Tuple[RelationSig, ...]
    accum: AccumPolicy = INT32_CHECKED
    k_bucket: int = 0

    @property
    def m(self) -> int:
        return len(self.dims)

    @property
    def has_hop(self) -> bool:
        """Does any CN slot carry a sub-dimension behind its parent?"""
        return any(d.parent >= 0 for d in self.dims)


def _route_sig(route: RelationRoute, domain: int, bucket: bool,
               key_width: int = 0, parent: int = -1,
               parent_key: int = 0) -> RelationSig:
    # descriptor metadata only — computing a signature must not materialize
    # the (lazy) column arrays
    S, L = route.ref.shard_rows, route.ref.text_len
    C = route.send.shape[-1]
    if bucket:
        S, C, L = bucket_pow2(S), bucket_pow2(C), bucket_pow2(L)
        domain = bucket_pow2(domain) if domain else 0
    return RelationSig(rows=S, cap=C, text_len=L, domain=domain,
                       key_width=key_width, parent=parent,
                       parent_key=parent_key)


def plan_signature(plan: CNPlan, bucket: bool = True,
                   accum: Optional[AccumPolicy] = None) -> PlanSignature:
    """``accum=None`` follows the process-wide ``jax_enable_x64`` flag
    (``AccumPolicy.current()``); sessions pass their resolved policy."""
    if accum is None:
        accum = AccumPolicy.current()

    def dim_sig(i):
        route = plan.dims[i]
        if i in plan.hops:
            parent, row = plan.hops[i]
            return _route_sig(route, plan.key_domains[i], bucket,
                              parent=plan.included.index(parent),
                              parent_key=row)
        width = route.ref.key_width if route.ref.role == "parent" else 0
        return _route_sig(route, plan.key_domains[i], bucket, width)

    dims = tuple(dim_sig(i) for i in plan.included)
    fact = _route_sig(plan.fact, 0, bucket,
                      key_width=plan.fact.ref.key_width)
    return PlanSignature(n_devices=plan.n_devices, vocab=plan.vocab_size,
                         fact=fact, dims=dims, accum=accum)


def _pad_route(route: RelationRoute, sig: RelationSig) -> Dict[str, np.ndarray]:
    """One relation's padded device arguments, columns lane-major
    (``text [P, L, S]``, keys ``[P, m, S]`` / ``[P, S]``)."""
    rtext, rkeys = route.text, route.keys   # materialize the lazy columns once
    P, S, L = rtext.shape
    text = np.pad(rtext, ((0, 0), (0, sig.rows - S), (0, sig.text_len - L)),
                  constant_values=PAD_ID)
    key_pad = ((0, 0), (0, sig.rows - S)) + ((0, 0),) * (rkeys.ndim - 2)
    keys = np.pad(rkeys, key_pad, constant_values=0)
    send = np.pad(route.send, ((0, 0), (0, 0), (0, sig.cap - route.send.shape[-1])),
                  constant_values=-1)
    return {"text": lane_major(text), "keys": lane_major(keys), "send": send}


def pad_plan_arrays(plan: CNPlan, sig: PlanSignature):
    """(fact, [dims]) numpy dicts padded to ``sig`` — same pytree layout as
    the unpadded device arguments."""
    fact = _pad_route(plan.fact, sig.fact)
    dims = [_pad_route(plan.dims[i], rsig)
            for i, rsig in zip(plan.included, sig.dims)]
    return fact, dims


def group_plan_indices(plans: Sequence[CNPlan], bucket: bool = True,
                       accum: Optional[AccumPolicy] = None
                       ) -> List[Tuple[PlanSignature, List[int]]]:
    """Group plan *indices* by signature (insertion order preserved): one
    batched device program per group."""
    groups: Dict[PlanSignature, List[int]] = {}
    for i, plan in enumerate(plans):
        groups.setdefault(plan_signature(plan, bucket, accum), []).append(i)
    return list(groups.items())


def group_plans(plans: Sequence[CNPlan], bucket: bool = True,
                accum: Optional[AccumPolicy] = None
                ) -> List[Tuple[PlanSignature, List[CNPlan]]]:
    """As ``group_plan_indices``, materialized to the plans themselves."""
    return [(sig, [plans[i] for i in idxs])
            for sig, idxs in group_plan_indices(plans, bucket, accum)]


def stack_group(plans: Sequence[CNPlan], sig: PlanSignature):
    """Stack same-signature plans along a leading CN axis: every leaf goes
    [P, ...] -> [N, P, ...]."""
    padded = [pad_plan_arrays(p, sig) for p in plans]
    fact = {k: np.stack([f[k] for f, _ in padded]) for k in ("text", "keys", "send")}
    dims = [{k: np.stack([d[j][k] for _, d in padded])
             for k in ("text", "keys", "send")} for j in range(sig.m)]
    return fact, dims


def pad_cn_axis(fact, dims, n_stack: int):
    """Pad the leading CN axis of a stacked group to ``n_stack`` with null
    plans: an all ``-1`` send table routes nothing, so a padded CN's masks,
    num-arrays, volumes and histogram are exactly zero (same invariants as
    the per-dim padding above).  Buckets the one data-dependent dim —
    dynamic-batching window size — that per-plan bucketing can't reach."""
    def pad(rel):
        n = rel["text"].shape[0]
        if n == n_stack:
            return rel
        fills = {"text": PAD_ID, "keys": 0, "send": -1}
        return {k: np.concatenate(
                    [v, np.full((n_stack - n,) + v.shape[1:], fills[k],
                                v.dtype)])
                for k, v in rel.items()}
    return pad(fact), [pad(d) for d in dims]
