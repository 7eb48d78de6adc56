"""Batched, cached FCT query execution engine.

The planner (core/plan.py) stays per-CN; this module owns everything after
planning:

  1. bucket every plan's data-dependent dims to a PlanSignature (batch.py),
  2. group same-signature CNs and stack them along a leading CN axis,
  3. run ONE shard_map program per group — the per-CN device body is vmapped
     over the CN axis, the [N, vocab] histograms are summed on device and
     cross-worker aggregation is a single collective: a vocab-sharded
     reduce-scatter on multi-device meshes (each device owns its vocab/P
     bin shard — half the all-reduce's traffic and no replicated result;
     the host gather reads each shard exactly once) with a psum fallback on
     one device — so a query costs one device dispatch and one host
     transfer per signature, not per CN,
  4. memoize the jitted executables in an ExecutableCache keyed by
     (signature, N, histogram backend, mesh), so warm queries never retrace,
  5. with a session's RelationStore (store.py), gather the tuple-set
     ``text``/``keys`` columns from DEVICE-RESIDENT arrays inside the
     shard_map program: the store uploads each tuple-set relation once per
     session, and a dispatch ships only the stacked send tables plus the
     fact key-column indices — kilobytes of routing metadata instead of
     megabytes of columns.  Because the store is content-addressed and
     composition-independent, multi-query per-CN batches reuse the same
     uploads as single-query dispatches (this subsumes the PR 3 stacked-
     array cache, whose reuse was limited to deterministic group
     compositions).

``run_plans`` returns the group-summed total (one vocab-sized transfer per
group); ``run_plans_individual`` keeps the per-CN axis on the output so CNs
from *different* queries can share one batched dispatch and still be
attributed back to their query — the multi-query path of the session API.

Integer histograms make the batched sum exactly associative: the engine's
``all_freqs`` is bit-identical to the sequential per-CN path as long as every
term's group total fits the histogram dtype.  Precision is governed by one
:class:`~repro.core.accum.AccumPolicy`, carried on the group's
``PlanSignature`` (so executables key on it): under ``INT32_CHECKED`` the
device programs — cross-CN group sum and psum included — accumulate in
int32 and the host collection raises OverflowError on wrap-around (negative
totals, a best-effort check: a total wrapping past 2^32 back to positive is
not detected); under ``INT64_EXACT`` (``jax_enable_x64``) everything
accumulates in int64.  Both widths ride the integer-exact fct_count kernel
on the pallas path (split-limb int32-pair accumulation, bit-identical to a
host integer accumulation — the float32-rounding caveat of the old kernel
is retired along with the forced int64 ref fallback).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.accum import AccumPolicy
from repro.core.plan import CNPlan
from repro.obs import default_registry
from repro.obs import span as obs_span
from repro.runtime.batch import (BUCKET_MIN, PlanSignature, RelationSig,
                                 bucket_pow2, group_plan_indices,
                                 pad_cn_axis, plan_signature, stack_group,
                                 x64_flag)
from repro.runtime.cache import ExecutableCache, default_cache


CN_BUCKET_MIN = 4  # floor for bucketing the per-CN-output programs' N axis
TOPK_BUCKET_MIN = 16  # floor for bucketing the fct_topk family's k axis
KW_BUCKET_MIN = 8  # floor for padding the keyword-exclusion id vector

#: structural filler for the fct_topk family's PlanSignature: the finalize
#: program reads no relations (its input is the already-aggregated
#: histogram), but the signature type is shared with the histogram families,
#: so the relation slot carries one fixed minimal shape.
_TOPK_REL = RelationSig(rows=BUCKET_MIN, cap=BUCKET_MIN, text_len=BUCKET_MIN)


def vocab_padded(vocab: int, n_devices: int) -> int:
    """Vocab rounded up so each device owns an equal ``vocab/P`` bin shard
    under reduce-scatter aggregation.  The pad bins are structurally zero
    (the histogram never writes past ``vocab``), so slicing them off on the
    host is exact."""
    return -(-vocab // n_devices) * n_devices


def _vmapped_cns(fact, dims, sig: PlanSignature, histogram_backend: str,
                 reduce_cns: bool, reduce_scatter: bool):
    """Per-device body shared by both program families: vmap the one-CN
    MR¹+MR² over the leading CN axis, then ONE cross-worker collective.

    The cross-CN group sum and the collective accumulate in the signature's
    AccumPolicy dtype — explicitly, so individually-fine int32 CNs summing
    past 2^31 wrap (and are caught on collection) under INT32_CHECKED and
    stay exact under INT64_EXACT, instead of depending on whatever dtype
    the per-CN histograms happened to carry.

    ``reduce_scatter=True`` replaces the full-vocab ``psum`` (an all-reduce:
    every device ends up holding all ``vocab`` bins, ~2·(P-1)/P·vocab moved
    per device plus a replicated result) with ``lax.psum_scatter`` over a
    vocab axis padded to a multiple of P: each device owns only its
    ``vocab/P`` bin shard — half the collective traffic, no broadcast of
    bins nobody reads, and the host gather touches each shard exactly once.
    Integer addition is associative, so both collectives produce
    bit-identical totals under either AccumPolicy."""
    from repro.core.fct import _device_fct_local
    domains = tuple(d.domain for d in sig.dims)

    def one_cn(f, ds):
        return _device_fct_local(f, ds, domains=domains, vocab=sig.vocab,
                                 histogram_backend=histogram_backend,
                                 accum=sig.accum)

    hists = jax.vmap(one_cn)(fact, dims)            # [N, vocab]
    acc = sig.accum.dtype
    pad = vocab_padded(sig.vocab, sig.n_devices) - sig.vocab
    with jax.named_scope("fct.reduce"):
        if reduce_cns:
            total = jnp.sum(hists, axis=0, dtype=acc)
            if not reduce_scatter:
                with jax.named_scope("fct.collective"):
                    return lax.psum(total, "w")
            if pad:
                total = jnp.pad(total, (0, pad))
            with jax.named_scope("fct.collective"):
                return lax.psum_scatter(total, "w", scatter_dimension=0,
                                        tiled=True)
        hists = hists.astype(acc)                   # per-CN, one collective
        if not reduce_scatter:
            with jax.named_scope("fct.collective"):
                return lax.psum(hists, "w")
        if pad:
            hists = jnp.pad(hists, ((0, 0), (0, pad)))
        with jax.named_scope("fct.collective"):
            return lax.psum_scatter(hists, "w", scatter_dimension=1,
                                    tiled=True)


def _out_spec(reduce_cns: bool, reduce_scatter: bool):
    """Output layout of a program family: replicated under psum, vocab-
    sharded over the worker axis under reduce-scatter (each device owns its
    ``vocab/P`` bin shard; the host-side gather then reads each shard from
    exactly one device)."""
    if not reduce_scatter:
        return P()
    return P("w") if reduce_cns else P(None, "w")


def _build_batched_fn(sig: PlanSignature, mesh: Mesh, histogram_backend: str,
                      reduce_cns: bool = True, reduce_scatter: bool = False):
    """shard_map program over host-stacked [N, P, ...] relations.

    ``reduce_cns=True``  -> freq[vocab]     (CN axis summed on device)
    ``reduce_cns=False`` -> freq[N, vocab]  (per-CN totals, for callers that
    attribute CNs of one batch to different queries)

    Under ``reduce_scatter`` the vocab axis is padded to a multiple of P and
    sharded ``P("w")`` on the output instead of replicated (see
    ``_vmapped_cns``); collection slices the pad bins off.
    """
    shard = P(None, "w")
    spec = {"text": shard, "keys": shard, "send": shard}

    def device_fn(fact, dims):
        with jax.named_scope("fct.stack"):
            fact = {k: jnp.squeeze(v, 1) for k, v in fact.items()}
            dims = [{k: jnp.squeeze(v, 1) for k, v in d.items()}
                    for d in dims]
        return _vmapped_cns(fact, dims, sig, histogram_backend, reduce_cns,
                            reduce_scatter)

    return shard_map(device_fn, mesh=mesh, in_specs=(spec, [spec] * sig.m),
                     out_specs=_out_spec(reduce_cns, reduce_scatter),
                     check_vma=False)


def _build_store_fn(sig: PlanSignature, mesh: Mesh, histogram_backend: str,
                    n_stack: int, reduce_cns: bool = True,
                    reduce_scatter: bool = False):
    """shard_map program whose relation columns are STORE-RESIDENT.

    Inputs per relation are ``n_stack`` separate device arrays (one per CN
    slot, each [P, S, ...] sharded P("w") and living in the session's
    RelationStore) plus the host-shipped stacked send tables; the fact
    additionally carries per-CN key-column indices that gather each CN's
    columns out of the full-width stored key matrix (core.fct._route_cn).
    The per-device body stacks its local shards along the CN axis and runs
    the same vmapped MR¹+MR² as the host-stacked family — outputs are
    bit-identical.
    """
    col = P("w")
    rel_spec = {"text": [col] * n_stack, "keys": [col] * n_stack,
                "send": P(None, "w")}
    fact_spec = dict(rel_spec)
    fact_spec["cols"] = P()

    def device_fn(fact, dims):
        def stack(rel):
            out = {"text": jnp.stack([jnp.squeeze(t, 0)
                                      for t in rel["text"]]),
                   "keys": jnp.stack([jnp.squeeze(k, 0)
                                      for k in rel["keys"]]),
                   "send": jnp.squeeze(rel["send"], 1)}
            if "cols" in rel:
                out["cols"] = rel["cols"]
            return out

        with jax.named_scope("fct.stack"):
            fact, dims = stack(fact), [stack(d) for d in dims]
        return _vmapped_cns(fact, dims, sig, histogram_backend, reduce_cns,
                            reduce_scatter)

    return shard_map(device_fn, mesh=mesh,
                     in_specs=(fact_spec, [rel_spec] * sig.m),
                     out_specs=_out_spec(reduce_cns, reduce_scatter),
                     check_vma=False)


def topk_signature(vocab: int, n_devices: int, accum: AccumPolicy,
                   k: int) -> PlanSignature:
    """Signature of the ``fct_topk`` finalize program for a top-``k``
    request.  ``k_bucket`` rounds ``k + 1`` up to a power of two (floor
    ``TOPK_BUCKET_MIN``): the ``+1`` keeps the (k+1)-th count in the
    candidate set — the threshold the pruning loop compares remaining group
    bounds against — and bucketing lets nearby k share one executable."""
    return PlanSignature(n_devices=n_devices, vocab=vocab, fact=_TOPK_REL,
                         dims=(), accum=accum,
                         k_bucket=bucket_pow2(k + 1, TOPK_BUCKET_MIN))


def k_effective(sig: PlanSignature) -> int:
    """Candidates the finalize program returns: ``k_bucket`` clamped to the
    vocab (a top-k past the vocab size is just the whole excluded vocab)."""
    return min(sig.k_bucket, sig.vocab)


def keyword_ids_array(keywords: Sequence[int]) -> np.ndarray:
    """Keyword-exclusion ids as int32, ``-1``-padded to a pow-2 width (the
    width rides the executable-cache key): ``-1`` never equals a vocab id,
    so pad slots exclude nothing."""
    kw_pad = bucket_pow2(max(len(keywords), 1), KW_BUCKET_MIN)
    out = np.full((kw_pad,), -1, np.int32)
    if len(keywords):
        out[:len(keywords)] = list(keywords)
    return out


def _build_topk_fn(sig: PlanSignature, mesh: Mesh, reduce_scatter: bool,
                   kw_pad: int):
    """shard_map finalize program of the ``fct_topk`` family.

    Input is the device-resident aggregated histogram (vocab-sharded
    ``P("w")`` under reduce-scatter, replicated otherwise) plus the keyword
    ids and an int8 stop/PAD exclusion vector in the same layout.  Each
    device:

      1. flags wrap-around (any negative bin) BEFORE exclusions — the
         INT32_CHECKED overflow check moves on device, so the host never
         has to read the O(vocab) histogram to enforce it,
      2. zeroes excluded bins (keywords by id equality, stopwords/PAD via
         the mask), matching the host oracle which zeroes before slicing,
         and sets reduce-scatter vocab-pad bins to ``-1`` so they sort
         strictly below every real (nonnegative, post-exclusion) bin,
      3. takes its local ``lax.top_k`` — O(k) candidates per device,
      4. ``all_gather``s the (count, id) candidates over the SMALL k axis
         (never the vocab axis) and re-``top_k``s the ``P * shard_k``
         candidates down to ``k_eff``.

    Tie-breaking is deterministic and equal to the host oracle's stable
    ``argsort(-f)``: ``lax.top_k`` prefers the lower index on equal values,
    shard-local indices map to ascending global ids, and the device-major
    ``all_gather`` concatenation keeps ids ascending within each count — so
    the winner of any tie is always the lowest term id, at every P.

    Replicated inputs (psum aggregation / P=1) skip the gather entirely:
    every device already holds all vocab bins, and gathering would
    duplicate each candidate P times.
    """
    vocab, n_dev = sig.vocab, sig.n_devices
    vp = vocab_padded(vocab, n_dev) if reduce_scatter else vocab
    shard = vp // n_dev if reduce_scatter else vocab
    k_eff = k_effective(sig)
    shard_k = min(k_eff, shard)
    acc = sig.accum.dtype

    def device_fn(hist, kw, excl):
        # hist [shard] acc · kw [kw_pad] int32 (-1 pads) · excl [shard] int8
        wrapped = jnp.any(hist < 0).astype(jnp.int32)
        ids = jnp.arange(shard, dtype=jnp.int32)
        if reduce_scatter:
            ids = ids + lax.axis_index("w").astype(jnp.int32) * shard
        is_kw = jnp.any(ids[:, None] == kw[None, :], axis=1)
        h = jnp.where(is_kw | (excl != 0), jnp.zeros((), acc), hist)
        if vp != vocab:
            h = jnp.where(ids >= vocab, -jnp.ones((), acc), h)
        v, local = lax.top_k(h, shard_k)
        cand = ids[local]
        if not reduce_scatter:
            return v[:k_eff], cand[:k_eff], wrapped
        with jax.named_scope("fct.collective"):
            av = lax.all_gather(v, "w", tiled=True)    # [P * shard_k]
            ai = lax.all_gather(cand, "w", tiled=True)
            aw = lax.all_gather(wrapped[None], "w", tiled=True)
        fv, pos = lax.top_k(av, k_eff)
        return fv, ai[pos], jnp.max(aw)

    def scoped_fn(hist, kw, excl):
        with jax.named_scope("fct.topk"):
            return device_fn(hist, kw, excl)

    hist_spec = P("w") if reduce_scatter else P()
    return shard_map(scoped_fn, mesh=mesh,
                     in_specs=(hist_spec, P(), hist_spec),
                     out_specs=(P(), P(), P()), check_vma=False)


@dataclasses.dataclass
class TopkPending:
    """Pending handle of :meth:`FCTEngine.dispatch_topk`: lazy O(k) device
    outputs plus the pruning ledger.  Block via
    :meth:`FCTEngine.collect_topk`."""

    counts: object        # lazy [k_eff] device array, policy dtype
    ids: object           # lazy [k_eff] int32 global term ids
    wrapped: object       # lazy scalar int32 overflow flag
    k_eff: int
    vocab: int
    groups_run: int
    groups_pruned: int
    pruned_rows: int


class FCTEngine:
    """Query execution runtime: shape-bucketed compile cache + batched
    multi-CN dispatch.

    ``batch=False`` dispatches one program per CN (still cached/bucketed);
    ``bucket=False`` keys on exact shapes (still cached/batched).  The
    default engine (``default_engine()``) shares the process-wide cache.

    ``bytes_shipped`` counts host→device argument bytes per dispatch;
    ``column_bytes_shipped`` is the text/keys portion of that — zero on the
    store path, where columns are device-resident (store uploads are
    accounted by the RelationStore itself).

    ``reduce_scatter=True`` (default) aggregates histograms with a vocab-
    sharded ``psum_scatter`` on meshes with more than one device — each
    device owns ``vocab/P`` bins instead of a replicated full-vocab
    all-reduce — and falls back to ``psum`` on a single device (where a
    collective is a no-op and the replicated layout is free).  Both
    aggregations are bit-identical; ``False`` forces psum everywhere (the
    equivalence baseline).  The choice is part of the executable-cache key.
    """

    def __init__(self, cache: Optional[ExecutableCache] = None,
                 batch: bool = True, bucket: bool = True,
                 reduce_scatter: bool = True, metrics=None) -> None:
        self.metrics = metrics if metrics is not None else default_registry()
        self.cache = cache if cache is not None else ExecutableCache(
            metrics=self.metrics)
        self.batch = batch
        self.bucket = bucket
        self.reduce_scatter = reduce_scatter
        # the default engine is shared process-wide (sessions, serving
        # tenants, sync callers); the registry lock guards the counters
        self._c_batches = self.metrics.counter("engine.batches_run")
        self._c_cns = self.metrics.counter("engine.cns_run")
        # relations (fact and dims, per CN slot) whose route is the identity
        # on a one-device mesh (core.fct._route): no gather, no all_to_all
        self._c_in_place = self.metrics.counter("engine.routes_in_place")
        self._c_bytes = self.metrics.counter("engine.bytes_shipped")
        self._c_column_bytes = self.metrics.counter(
            "engine.column_bytes_shipped")
        self._c_d2h = self.metrics.counter("engine.device_to_host_bytes")
        self._c_groups_pruned = self.metrics.counter("engine.groups_pruned")
        self._c_pruned_rows = self.metrics.counter("engine.pruned_rows")

    # legacy attribute views over the registry-owned counters
    @property
    def batches_run(self) -> int:
        return self._c_batches.value

    @property
    def cns_run(self) -> int:
        return self._c_cns.value

    @property
    def bytes_shipped(self) -> int:
        return self._c_bytes.value

    @property
    def column_bytes_shipped(self) -> int:
        return self._c_column_bytes.value

    @property
    def device_to_host_bytes(self) -> int:
        return self._c_d2h.value

    def _group(self, plans: Sequence[CNPlan],
               accum: Optional[AccumPolicy] = None
               ) -> List[Tuple[PlanSignature, List[int]]]:
        """Signature groups as plan indices; singletons when unbatched."""
        if not self.batch:
            return [(plan_signature(p, self.bucket, accum), [i])
                    for i, p in enumerate(plans)]
        return group_plan_indices(plans, self.bucket, accum)

    def _dispatch(self, sig: PlanSignature, group: Sequence[CNPlan],
                  mesh: Mesh, histogram_backend: str, reduce_cns: bool,
                  store=None):
        """Span shell around :meth:`_dispatch_group`: one
        ``engine.dispatch_group`` span per launch on the active trace (and,
        through the span annotator, on the profiler's host plane)."""
        path = "store" if store is not None else "host"
        family = "sum" if reduce_cns else "percn"
        with obs_span("engine.dispatch_group", n_cns=len(group), path=path,
                      family=family, n_devices=sig.n_devices):
            return self._dispatch_group(sig, group, mesh, histogram_backend,
                                        reduce_cns, store)

    def _dispatch_group(self, sig: PlanSignature, group: Sequence[CNPlan],
                        mesh: Mesh, histogram_backend: str, reduce_cns: bool,
                        store=None):
        """Enqueue one stacked group on the device; returns the LAZY result
        (jax async dispatch) — callers block via ``_collect``.

        The per-CN-output family additionally rounds the CN axis up to a
        multiple of CN_BUCKET_MIN (zero-contribution null-plan padding): its
        group sizes vary with the caller's batch composition, and without
        rounding every size would compile a fresh program variant.  Padded
        compute is capped at CN_BUCKET_MIN - 1 null CNs per group.  The
        summed family keeps exact N (deterministic per request, no padded
        compute on the latency-critical single-query path).

        With a ``store`` (RelationStore), relation columns are gathered from
        device-resident arrays: only the send tables and fact key-column
        indices are shipped per dispatch; warm dispatches (store hits) ship
        ZERO column bytes.  Without one, the legacy host pad/stack path is
        used (the pre-store engine — kept as the equivalence baseline and
        for storeless callers).

        Two child spans split the host work: ``store.send_tables`` (the
        numpy pad-and-stack of send tables and key-column indices; the host
        path's whole ``stack_group``) and ``engine.enqueue`` (the jitted
        call: argument conversion and the host-to-device transfer it
        starts).
        """
        n_stack = len(group)
        if not reduce_cns and self.bucket:
            n_stack = -(-n_stack // CN_BUCKET_MIN) * CN_BUCKET_MIN
        x64 = x64_flag()
        # vocab-sharded reduce-scatter only pays (and only differs from
        # psum) on real multi-device meshes; the aggregation kind rides the
        # cache key so both program variants can coexist
        rs = self.reduce_scatter and sig.n_devices > 1
        agg = "rs" if rs else "psum"
        if store is not None:
            from repro.runtime.store import store_group_args
            (fact, dims), shipped = store_group_args(store, group, sig,
                                                     n_stack)
            kind = "fct_store" if reduce_cns else "fct_store_percn"
            key = (kind, sig, n_stack, histogram_backend, mesh, x64, agg)
            fn = self.cache.get_or_build(
                key, lambda: _build_store_fn(sig, mesh, histogram_backend,
                                             n_stack,
                                             reduce_cns=reduce_cns,
                                             reduce_scatter=rs))
            self._c_bytes.inc(shipped)
        else:
            with obs_span("store.send_tables", n_stack=n_stack):
                fact, dims = stack_group(group, sig)
                if n_stack > len(group):
                    fact, dims = pad_cn_axis(fact, dims, n_stack)
            kind = "fct_batched" if reduce_cns else "fct_batched_percn"
            key = (kind, sig, n_stack, histogram_backend, mesh, x64, agg)
            fn = self.cache.get_or_build(
                key, lambda: _build_batched_fn(sig, mesh, histogram_backend,
                                               reduce_cns=reduce_cns,
                                               reduce_scatter=rs))
            shipped = sum(v.nbytes for v in fact.values()) + sum(
                v.nbytes for d in dims for v in d.values())
            columns = shipped - fact["send"].nbytes - sum(
                d["send"].nbytes for d in dims)
            self._c_bytes.inc(shipped)
            self._c_column_bytes.inc(columns)
        with obs_span("engine.enqueue", kind=kind):
            out = fn(fact, dims)
        self._c_batches.inc()
        self._c_cns.inc(len(group))
        if sig.n_devices == 1:
            self._c_in_place.inc(n_stack * (1 + sig.m))
        return out

    def _collect(self, lazy) -> np.ndarray:
        raw = np.asarray(lazy)
        self._c_d2h.inc(raw.nbytes)
        # the dtype IS the policy on the collection side: int32 results were
        # accumulated under INT32_CHECKED, whose contract is to fail loudly
        # on wrap-around instead of returning silently wrong counts
        AccumPolicy.for_dtype(raw.dtype).check_totals(raw)
        return raw.astype(np.int64)

    def dispatch_plans(self, plans: Sequence[CNPlan], mesh: Mesh,
                       histogram_backend: str = "auto",
                       individual: bool = False, store=None,
                       accum: Optional[AccumPolicy] = None):
        """Async half of a run: enqueue every signature group and return a
        pending handle ``[(plan_indices, lazy_result), ...]``.

        Device compute of ALL groups proceeds concurrently (and overlaps
        whatever the host does next); block with ``collect_total`` /
        ``collect_individual``.  ``individual=True`` keeps the per-CN output
        axis so CNs of different queries can share a dispatch.

        ``store`` (a RelationStore bound to this mesh) makes relation
        columns device-resident: each tuple-set relation is uploaded once
        and referenced by every later dispatch — across warm repeats,
        program families, AND batch compositions (content-addressed, unlike
        the retired PR 3 stack cache, which was limited to deterministic
        single-query groups).

        ``accum`` pins the AccumPolicy (int32-checked / int64-exact) the
        device programs accumulate under; ``None`` follows the process-wide
        ``jax_enable_x64`` flag.  The policy rides each group's signature,
        so executables compiled under different policies never alias.
        """
        if not plans:
            raise ValueError("dispatch_plans needs at least one plan")
        return [(idxs, self._dispatch(sig, [plans[i] for i in idxs], mesh,
                                      histogram_backend,
                                      reduce_cns=not individual,
                                      store=store))
                for sig, idxs in self._group(plans, accum)]

    def collect_total(self, pending, vocab: int) -> np.ndarray:
        """Block on an ``individual=False`` handle: total freq[vocab].

        Reduce-scattered results arrive vocab-sharded and padded to a
        multiple of P; the gather reads each device's owned shard once and
        the (structurally zero) pad bins are sliced off."""
        total = np.zeros((vocab,), np.int64)
        for _, lazy in pending:
            total += self._collect(lazy)[:vocab]
        return total

    def collect_individual(self, pending, n_plans: int,
                           vocab: int) -> np.ndarray:
        """Block on an ``individual=True`` handle: freq[n_plans, vocab]."""
        out = np.zeros((n_plans, vocab), np.int64)
        for idxs, lazy in pending:
            # drop the CN-axis pad and the reduce-scatter vocab pad
            out[idxs] = self._collect(lazy)[:len(idxs), :vocab]
        return out

    def vocab_device_vector(self, vec: np.ndarray, mesh: Mesh,
                            dtype) -> jax.Array:
        """Upload a host ``[vocab]`` vector in the engine's aggregation
        layout — the layout group outputs arrive in: vocab-sharded
        ``P("w")`` zero-padded to a multiple of P under reduce-scatter,
        replicated otherwise — so the caller can add it to (or feed it
        beside) device-resident histograms.  Counted as shipped bytes."""
        rs = self.reduce_scatter and mesh.size > 1
        arr = vec.astype(dtype, copy=True)
        if rs:
            vp = vocab_padded(len(arr), mesh.size)
            if vp != len(arr):
                arr = np.pad(arr, (0, vp - len(arr)))
            sharding = NamedSharding(mesh, P("w"))
        else:
            sharding = NamedSharding(mesh, P())
        self._c_bytes.inc(arr.nbytes)
        return jax.device_put(arr, sharding)

    @staticmethod
    def _plan_rows(plans: Sequence[CNPlan], idxs: Sequence[int]) -> int:
        """Total routed fact rows of a set of plans (pruning ledger)."""
        return int(sum(int(plans[i].device_rows.sum()) for i in idxs
                       if plans[i].device_rows is not None))

    def dispatch_topk(self, plans: Sequence[CNPlan], mesh: Mesh, k: int, *,
                      keywords: Sequence[int] = (), excl=None,
                      host_extra=None, histogram_backend: str = "auto",
                      store=None, accum: Optional[AccumPolicy] = None,
                      prune: str = "zero") -> TopkPending:
        """Async top-k run: dispatch every signature group, keep the
        aggregated histogram DEVICE-RESIDENT (group outputs are summed with
        eager sharded adds, never transferred), and finalize with the
        ``fct_topk`` program — the pending handle resolves to O(k)
        candidates, not the O(vocab) histogram.

        ``prune`` is the cross-CN-group pruning mode, bounding each group's
        maximum possible contribution by its plans' total volume-weighted
        token mass (``CNPlan.contrib_bound``, computed from the same
        routing volumes that fill ``device_rows``):

        * ``"off"`` — dispatch every group.
        * ``"zero"`` (default) — skip groups whose summed bound is exactly
          0.0: they provably contribute nothing to any term, so results
          stay bit-identical to the unpruned path.
        * ``"threshold"`` — additionally process groups in descending
          bound order and, after each, probe the running k-th and (k+1)-th
          counts (an O(k) transfer); once ``θ_k > θ_{k+1} + Σ remaining
          bounds``, no remaining group can displace any current top-k term
          and the whole suffix is skipped.  The top-k SET is exact; the
          reported counts/order are those of the processed prefix (lower
          bounds), which is why this mode is opt-in.

        ``keywords`` and ``excl`` (an int8 stop/PAD mask from
        :meth:`vocab_device_vector`) reproduce the host oracle's exclusions
        on device; ``host_extra`` is an optional device-resident histogram
        in the same layout added to the group total — sessions use it for
        map-only single-relation CNs, which have no routed plans.
        """
        if not plans:
            raise ValueError("dispatch_topk needs at least one plan")
        if prune not in ("off", "zero", "threshold"):
            raise ValueError(f"unknown prune mode {prune!r}")
        vocab = plans[0].vocab_size
        rs = self.reduce_scatter and mesh.size > 1
        groups = self._group(plans, accum)
        sig0 = groups[0][0]
        tsig = topk_signature(vocab, sig0.n_devices, sig0.accum, k)
        kw = keyword_ids_array(keywords)
        if excl is None:
            excl = self.vocab_device_vector(np.zeros(vocab, np.int8), mesh,
                                            np.int8)
        agg = "rs" if rs else "psum"
        key = ("fct_topk", tsig, len(kw), mesh, x64_flag(), agg)
        topk_fn = self.cache.get_or_build(
            key, lambda: _build_topk_fn(tsig, mesh, rs, len(kw)))
        self._c_bytes.inc(kw.nbytes)

        bounds = [sum(plans[i].contrib_bound for i in idxs)
                  for _, idxs in groups]
        run_list = list(range(len(groups)))
        g_pruned = rows_pruned = 0
        if prune != "off":
            keep = [g for g in run_list if bounds[g] != 0.0]
            zero = [g for g in run_list if bounds[g] == 0.0]
            if not keep and host_extra is None and zero:
                # keep one group so a device histogram exists at all
                keep, zero = zero[:1], zero[1:]
            for g in zero:
                g_pruned += 1
                rows_pruned += self._plan_rows(plans, groups[g][1])
            run_list = keep
        if prune == "threshold":
            run_list.sort(key=lambda g: -bounds[g])

        total = host_extra
        groups_run = 0
        kk = min(k, vocab)
        for pos, g in enumerate(run_list):
            sig, idxs = groups[g]
            lazy = self._dispatch(sig, [plans[i] for i in idxs], mesh,
                                  histogram_backend, reduce_cns=True,
                                  store=store)
            total = lazy if total is None else total + lazy
            groups_run += 1
            rest = run_list[pos + 1:]
            if prune == "threshold" and rest and kk + 1 <= tsig.k_bucket:
                # O(k) probe of the running counts: prune the suffix once
                # even its combined mass cannot displace the k-th count
                head = np.asarray(topk_fn(total, kw, excl)[0])
                self._c_d2h.inc(head.nbytes)
                b_rest = sum(bounds[r] for r in rest)
                if kk < len(head) and \
                        float(head[kk - 1]) > float(head[kk]) + b_rest:
                    for r in rest:
                        g_pruned += 1
                        rows_pruned += self._plan_rows(plans, groups[r][1])
                    break

        with obs_span("engine.topk_finalize", k=k, k_eff=k_effective(tsig),
                      n_groups=len(groups), groups_pruned=g_pruned):
            counts, ids, wrapped = topk_fn(total, kw, excl)
        if g_pruned:
            self._c_groups_pruned.inc(g_pruned)
            self._c_pruned_rows.inc(rows_pruned)
        return TopkPending(counts=counts, ids=ids, wrapped=wrapped,
                           k_eff=k_effective(tsig), vocab=vocab,
                           groups_run=groups_run, groups_pruned=g_pruned,
                           pruned_rows=rows_pruned)

    def collect_topk(self, tp: TopkPending
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """Block on a :meth:`dispatch_topk` handle:
        ``(term_ids[k_eff], counts[k_eff])`` int64, exclusion-masked and
        tie-broken by lowest term id — the O(k) transfer this family
        exists for.  Raises OverflowError when the device-side wrap flag
        is set (the INT32_CHECKED contract, checked on device over the
        full histogram)."""
        counts = np.asarray(tp.counts)
        ids = np.asarray(tp.ids)
        wrapped = np.asarray(tp.wrapped)
        self._c_d2h.inc(counts.nbytes + ids.nbytes + wrapped.nbytes)
        if int(wrapped):
            # same failure contract/message as the host-side wrap check
            AccumPolicy.for_dtype(counts.dtype).check_totals(
                np.full((1,), -1, counts.dtype))
        return ids.astype(np.int64), counts.astype(np.int64)

    def run_plans(self, plans: Sequence[CNPlan], mesh: Mesh,
                  histogram_backend: str = "auto", store=None,
                  accum: Optional[AccumPolicy] = None) -> np.ndarray:
        """Total freq[vocab] (int64) over all joined-CN plans."""
        pending = self.dispatch_plans(plans, mesh, histogram_backend,
                                      store=store, accum=accum)
        return self.collect_total(pending, plans[0].vocab_size)

    def run_plans_individual(self, plans: Sequence[CNPlan], mesh: Mesh,
                             histogram_backend: str = "auto",
                             store=None,
                             accum: Optional[AccumPolicy] = None
                             ) -> np.ndarray:
        """Per-plan freq[len(plans), vocab] (int64).

        Plans from different queries may share one device dispatch (same
        signature -> one stacked program); the per-CN output axis lets the
        caller attribute each histogram to its owning query.
        """
        pending = self.dispatch_plans(plans, mesh, histogram_backend,
                                      individual=True, store=store,
                                      accum=accum)
        return self.collect_individual(pending, len(plans),
                                       plans[0].vocab_size)

    def stats(self) -> dict:
        out = self.cache.stats()
        (batches, cns, shipped, columns, d2h, g_pruned, rows_pruned,
         in_place) = self.metrics.values(
            self._c_batches, self._c_cns, self._c_bytes,
            self._c_column_bytes, self._c_d2h, self._c_groups_pruned,
            self._c_pruned_rows, self._c_in_place)
        out.update(batches_run=batches, cns_run=cns, bytes_shipped=shipped,
                   column_bytes_shipped=columns, device_to_host_bytes=d2h,
                   groups_pruned=g_pruned, pruned_rows=rows_pruned,
                   routes_in_place=in_place)
        return out


_DEFAULT_ENGINE: Optional[FCTEngine] = None


def default_engine() -> FCTEngine:
    """Process-wide engine (shared executable cache): repeated queries from
    anywhere in the process amortize each other's compilations."""
    global _DEFAULT_ENGINE
    if _DEFAULT_ENGINE is None:
        _DEFAULT_ENGINE = FCTEngine(cache=default_cache())
    return _DEFAULT_ENGINE
