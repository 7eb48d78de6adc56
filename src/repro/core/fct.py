"""The two MapReduce jobs as one fused shard_map program (paper §4.3–§4.4).

MR¹ (statistics): route tuple-set rows per the static plan (gather →
``all_to_all`` → mask; in place on a one-device mesh), build dense
``num``-arrays per dimension, probe them per fact row to produce fact
volumes and per-dimension ``vol`` contributions.

MR² (term frequency): weighted token histogram of every routed payload with
its volume (Pallas ``fct_count`` on TPU, segment-sum ref elsewhere), then one
``psum`` over the worker axis — the "aggregation equal transformation" of
Theorem 1 — and a host-side top-k with the Def. 6 exclusions.

The two jobs are separable (``job1`` returns the vol-array artifact that
``job2`` consumes) so the MR¹→MR² boundary can be checkpointed, but the fused
path is the default: on a TPU there is no reason to spill the intermediate.

Each stage of the device body runs under a ``jax.named_scope``, so every op's
HLO ``op_name`` (and a profile's op metadata) names its stage: ``fct.stack``
(CN slots and the fact's key-column select), ``fct.route`` (send-table
gathers and masks; on one device the masks and row cuts alone),
``fct.mr1`` (num-arrays, probes, volumes; inside it ``fct.hop``, a
sub-dimension's num-array and volumes passed through its parent),
``fct.mr2`` (histogram inputs
and the ``fct_count`` calls), ``fct.reduce`` (cross-CN sum, accumulator
casts, vocab pads), ``fct.topk`` (the finalize program) and
``fct.collective`` (every cross-device collective, scoped at its call site
so it is the innermost scope of the op).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.accum import AccumPolicy
from repro.core.plan import CNPlan, lane_major
from repro.data.schema import PAD_ID, StarSchema
from repro.kernels.fct_count.ops import weighted_histogram


# ---------------------------------------------------------------------------
# device-side program
# ---------------------------------------------------------------------------

def _acc_dtype(accum: Optional[AccumPolicy] = None):
    """Volume/histogram accumulator dtype (read at trace time).

    The device bodies receive an explicit :class:`AccumPolicy` from the
    runtime engine (``PlanSignature.accum``); paths without one (the seed
    per-CN and two-job programs) follow the process-wide ``jax_enable_x64``
    flag, which every memoizing cache key includes.
    """
    return (accum or AccumPolicy.current()).dtype


def _fit_rows(col, c: int, fill: int):
    """``col`` cut or padded along its row (last) axis to ``c`` rows."""
    s = col.shape[-1]
    if c <= s:
        return col[..., :c]
    return jnp.pad(col, ((0, 0),) * (col.ndim - 1) + ((0, c - s),),
                   constant_values=fill)


def _route(text, keys, send):
    """Gather rows into per-destination buffers and all_to_all them.

    Columns are lane-major (rows on the LAST axis; see
    ``repro.core.plan.lane_major``): text [L, S]; keys [S] or [m, S];
    send [P, C] (local row idx, -1 pad).  Returns (text [L, P*C],
    keys [P*C] / [m, P*C], mask [P*C]) of received rows.

    With one destination (``P == 1``, a static shape) the shuffle is the
    identity: the planner keeps every row at its own slot (``send[0, c]``
    is ``c`` or -1, ``core.plan._send_table``), so the routed columns are
    the relation's own, cut or padded to ``C`` rows, and nothing is
    gathered or exchanged.  Slots the mask drops carry weight 0 downstream,
    whatever their contents.
    """
    p, c = send.shape
    with jax.named_scope("fct.route"):
        if p == 1:
            return (_fit_rows(text, c, PAD_ID), _fit_rows(keys, c, 0),
                    (send >= 0).reshape(c))
        idx = jnp.maximum(send, 0).reshape(-1)
        mask = send >= 0
        btext = jnp.take(text, idx, axis=-1).reshape(text.shape[:-1] + (p, c))
        bkeys = jnp.take(keys, idx, axis=-1).reshape(keys.shape[:-1] + (p, c))
        with jax.named_scope("fct.collective"):
            rtext = lax.all_to_all(btext, "w", split_axis=btext.ndim - 2,
                                   concat_axis=btext.ndim - 2, tiled=True)
            rkeys = lax.all_to_all(bkeys, "w", split_axis=bkeys.ndim - 2,
                                   concat_axis=bkeys.ndim - 2, tiled=True)
            rmask = lax.all_to_all(mask, "w", split_axis=0, concat_axis=0,
                                   tiled=True)
        return (rtext.reshape(text.shape[:-1] + (p * c,)),
                rkeys.reshape(keys.shape[:-1] + (p * c,)),
                rmask.reshape(p * c))


def _route_cn(fact, dims):
    """MR¹ shuffle stage shared by the fused, two-job and store paths: route
    every relation of one CN per its static send table.

    ``fact["keys"]`` is either the CN's selected key columns ``[m, S]`` (host
    paths) or the FULL-width store-resident matrix ``[m_all, S]`` with
    ``fact["cols"]`` naming the CN's columns — the store uploads each fact
    tuple set once and every CN over it selects its columns on device.
    """
    fkeys = fact["keys"]
    if "cols" in fact:
        with jax.named_scope("fct.stack"):
            fkeys = jnp.take(fkeys, fact["cols"], axis=0)
    routed_fact = _route(fact["text"], fkeys, fact["send"])
    routed_dims = [_route(d["text"], d["keys"], d["send"]) for d in dims]
    return routed_fact, routed_dims


def _mr1_volumes(routed_fact, routed_dims, domains: Tuple[int, ...],
                 accum: Optional[AccumPolicy] = None,
                 links: Tuple[Tuple[int, int], ...] = ()):
    """MR¹ statistics on routed relations: num-arrays (combine + reduce-side
    counting), then fact volume and per-dimension vol contributions
    (Algorithm 3 stage 2).  Returns (vol_fact, dim_vols).

    ``links[i]`` is ``(parent slot, parent key row)`` of a hop
    sub-dimension (``(-1, 0)`` for a dimension of the root; empty for a
    star): under ``fct.hop`` the sub-dimension's num-array is gathered at
    its parent's routed FK and multiplies the parent's rows into the
    parent's num-array (upward), and the parent rows' volumes without it
    are scatter-added into its key domain and gathered at its rows
    (downward).  The root's dimensions come first in ``routed_dims``, in
    the order of the fact's key columns."""
    acc = _acc_dtype(accum)
    parent = [p for p, _ in links] or [-1] * len(routed_dims)
    top = [i for i, p in enumerate(parent) if p < 0]
    with jax.named_scope("fct.mr1"):
        ftext, fkeys, fmask = routed_fact
        m = len(top)
        # upward: each parent row's count of sub-dimension rows it joins
        ups = {}                    # parent slot -> [(sub slot, gathered)]
        for c, (p, row) in enumerate(links):
            if p < 0:
                continue
            with jax.named_scope("fct.hop"):
                (ctext, ckeys, cmask) = routed_dims[c]
                cnum = jnp.zeros((domains[c],), acc).at[ckeys].add(
                    cmask.astype(acc), mode="drop")
                ups.setdefault(p, []).append(
                    (c, cnum[routed_dims[p][1][row]]))
        nums = []
        for i in top:
            (dtext, dkeys, dmask), dom = routed_dims[i], domains[i]
            if i not in ups:
                nums.append(jnp.zeros((dom,), jnp.int32).at[dkeys].add(
                    dmask.astype(jnp.int32), mode="drop"))
                continue
            with jax.named_scope("fct.hop"):
                w = dmask.astype(acc)
                for _, g in ups[i]:
                    w = w * g
                nums.append(jnp.zeros((dom,), acc).at[dkeys[0]].add(
                    w, mode="drop"))
        probes = [nums[i][fkeys[i]].astype(acc) for i in range(m)]
        fvalid = fmask.astype(acc)
        vol_fact = fvalid
        for pr in probes:
            vol_fact = vol_fact * pr
        dim_vols = [None] * len(routed_dims)
        for j, i in enumerate(top):
            others = fvalid
            for k in range(m):
                if k != j:
                    others = others * probes[k]
            contrib = jnp.zeros((domains[i],), acc).at[fkeys[j]].add(
                others, mode="drop")
            (dtext, dkeys, dmask) = routed_dims[i]
            if i not in ups:
                dim_vols[i] = contrib[dkeys] * dmask.astype(acc)
                continue
            base = contrib[dkeys[0]] * dmask.astype(acc)
            vol = base
            for _, g in ups[i]:
                vol = vol * g
            dim_vols[i] = vol
            # downward: the parent rows' volumes without each sub-dim
            with jax.named_scope("fct.hop"):
                for c, _ in ups[i]:
                    rest = base
                    for c2, g in ups[i]:
                        if c2 != c:
                            rest = rest * g
                    (ctext, ckeys, cmask) = routed_dims[c]
                    down = jnp.zeros((domains[c],), acc).at[
                        dkeys[links[c][1]]].add(rest, mode="drop")
                    dim_vols[c] = down[ckeys] * cmask.astype(acc)
    return vol_fact, dim_vols


def _device_fct_local(fact, dims, *, domains: Tuple[int, ...], vocab: int,
                      histogram_backend: str,
                      accum: Optional[AccumPolicy] = None,
                      links: Tuple[Tuple[int, int], ...] = ()):
    """One worker's MR¹+MR² for one CN, WITHOUT the final cross-worker psum
    (the runtime engine vmaps this over a batch of CNs and psums once).

    ``accum`` pins the volume/histogram dtype (int32-checked or int64-exact);
    integer weights of either width ride the integer-exact fct_count kernel
    on the pallas path.  ``links`` marks hop sub-dimensions
    (:func:`_mr1_volumes`)."""
    routed_fact, routed_dims = _route_cn(fact, dims)
    vol_fact, dim_vols = _mr1_volumes(routed_fact, routed_dims, domains,
                                      accum, links)
    ftext = routed_fact[0]

    # --- MR2: weighted histograms + global aggregation ---
    with jax.named_scope("fct.mr2"):
        hist = weighted_histogram(ftext, vol_fact, vocab,
                                  backend=histogram_backend)
        for (dtext, dkeys, dmask), w in zip(routed_dims, dim_vols):
            hist = hist + weighted_histogram(dtext, w.astype(hist.dtype),
                                             vocab, backend=histogram_backend)
    return hist


def _device_fct(fact, dims, *, domains: Tuple[int, ...], vocab: int,
                histogram_backend: str):
    """One worker's MR¹+MR² for one CN.  All inputs are this device's shard."""
    # the cast is a trace-time no-op (the local histogram already carries
    # the policy dtype) but pins the collective's accumulator width HERE,
    # where the psum is, instead of inheriting it from upstream
    hist = _device_fct_local(fact, dims, domains=domains, vocab=vocab,
                             histogram_backend=histogram_backend)
    with jax.named_scope("fct.collective"):
        return lax.psum(hist.astype(_acc_dtype()), "w")


def _plan_to_arrays(plan: CNPlan):
    def arrays(route):
        return {"text": jnp.asarray(lane_major(route.text)),
                "keys": jnp.asarray(lane_major(route.keys)),
                "send": jnp.asarray(route.send)}
    return arrays(plan.fact), [arrays(plan.dims[i]) for i in plan.included]


def _refuse_hop(plan: CNPlan, what: str) -> None:
    """Only the engine's store-path families run a hop: the seed and
    two-job programs take a star's columns."""
    if plan.hops:
        raise NotImplementedError(
            f"{what} runs star CNs only; a CN with a sub-dimension hop runs "
            "through FCTEngine with a RelationStore (an FCTSession)")


def make_fct_program(plan: CNPlan, mesh: Mesh, histogram_backend: str = "auto"):
    """shard_map'ed (fact, dims) -> freq[vocab], plus its input arrays."""
    _refuse_hop(plan, "make_fct_program")
    fact, dims = _plan_to_arrays(plan)
    domains = tuple(plan.key_domains[i] for i in plan.included)
    shard = P("w")
    specs_rel = {"text": shard, "keys": shard, "send": shard}
    # fct-lint: waive[R1] -- seed equivalence baseline: one program per call by design; tests diff it against the cached engine
    fn = shard_map(
        lambda f, ds: _device_fct(
            {k: jnp.squeeze(v, 0) for k, v in f.items()},
            [{k: jnp.squeeze(v, 0) for k, v in d.items()} for d in ds],
            domains=domains, vocab=plan.vocab_size,
            histogram_backend=histogram_backend),
        mesh=mesh,
        in_specs=(specs_rel, [specs_rel] * len(dims)),
        out_specs=P(),
        check_vma=False,
    )
    return fn, (fact, dims)


def run_cn_plan(plan: CNPlan, mesh: Mesh,
                histogram_backend: str = "auto") -> np.ndarray:
    fn, args = make_fct_program(plan, mesh, histogram_backend)
    # fct-lint: waive[R1] -- equivalence baseline entry point; retraces per call are the point of comparison, not a leak
    freq = jax.jit(fn)(*args)
    return np.asarray(freq, np.int64)


# ---------------------------------------------------------------------------
# split two-job execution (the paper's MR1 / MR2 boundary, checkpointable)
# ---------------------------------------------------------------------------

def _device_job1(fact, dims, *, domains):
    """MR1 only: route + num-arrays + volumes (via the shared `_route_cn` /
    `_mr1_volumes` helpers).  Returns the vol-arrays artifact {text, vol}
    per relation — the paper's reducer output that MapReduce2nd consumes
    (and the natural checkpoint boundary)."""
    routed_fact, routed_dims = _route_cn(fact, dims)
    vol_fact, dim_vols = _mr1_volumes(routed_fact, routed_dims, domains)
    return {"fact": {"text": routed_fact[0], "vol": vol_fact},
            "dims": [{"text": dtext, "vol": w}
                     for (dtext, dkeys, dmask), w
                     in zip(routed_dims, dim_vols)]}


def _device_job2(vol_arrays, *, vocab, histogram_backend):
    """MR2 only: weighted word-count over the vol-arrays + global psum."""
    with jax.named_scope("fct.mr2"):
        hist = weighted_histogram(vol_arrays["fact"]["text"],
                                  vol_arrays["fact"]["vol"], vocab,
                                  backend=histogram_backend)
        for d in vol_arrays["dims"]:
            hist = hist + weighted_histogram(d["text"],
                                             d["vol"].astype(hist.dtype),
                                             vocab, backend=histogram_backend)
    # same contract as _device_fct: the collective's accumulator width is
    # pinned at the collective, not inherited from the weight dtype
    with jax.named_scope("fct.collective"):
        return lax.psum(hist.astype(_acc_dtype()), "w")


def run_cn_plan_two_jobs(plan: CNPlan, mesh: Mesh,
                         histogram_backend: str = "auto",
                         checkpoint_dir: Optional[str] = None,
                         cache=None) -> np.ndarray:
    """MR1 -> (optional host checkpoint) -> MR2, matching the fused path.

    Both jobs' executables live in the runtime's shared compile cache (keyed
    by the plan's bucketed shape signature), so repeated plans re-jit nothing.
    """
    from repro.runtime.batch import pad_plan_arrays, plan_signature, x64_flag
    from repro.runtime.cache import default_cache
    _refuse_hop(plan, "run_cn_plan_two_jobs")
    if cache is None:
        cache = default_cache()
    sig = plan_signature(plan)
    fact, dims = pad_plan_arrays(plan, sig)
    domains = tuple(d.domain for d in sig.dims)
    m = sig.m
    shard = P("w")
    specs_rel = {"text": shard, "keys": shard, "send": shard}
    vol_spec = {"fact": {"text": shard, "vol": shard},
                "dims": [{"text": shard, "vol": shard}] * m}
    x64 = x64_flag()
    job1 = cache.get_or_build(
        ("fct_job1", sig, mesh, x64),
        # fct-lint: waive[R1] -- builder runs inside the shared signature-keyed ExecutableCache: warm plans never retrace
        lambda: shard_map(
            lambda f, ds: _device_job1(
                {k: jnp.squeeze(v, 0) for k, v in f.items()},
                [{k: jnp.squeeze(v, 0) for k, v in d.items()} for d in ds],
                domains=domains),
            mesh=mesh, in_specs=(specs_rel, [specs_rel] * m),
            out_specs=vol_spec, check_vma=False))
    vol_arrays = job1(fact, dims)
    if checkpoint_dir is not None:  # the MR boundary the paper spills to DFS
        from repro.distributed.checkpoint import (restore_checkpoint,
                                                  save_checkpoint)
        save_checkpoint(checkpoint_dir, 1, vol_arrays)
        _, vol_arrays = restore_checkpoint(checkpoint_dir, vol_arrays)
    job2 = cache.get_or_build(
        ("fct_job2", sig, histogram_backend, mesh, x64),
        # fct-lint: waive[R1] -- builder runs inside the shared signature-keyed ExecutableCache: warm plans never retrace
        lambda: shard_map(
            lambda va: _device_job2(va, vocab=plan.vocab_size,
                                    histogram_backend=histogram_backend),
            mesh=mesh, in_specs=(vol_spec,), out_specs=P(), check_vma=False))
    freq = job2(vol_arrays)
    return np.asarray(freq, np.int64)


def lower_cn_plan(plan: CNPlan, mesh: Mesh, histogram_backend: str = "auto"):
    """Lowered (uncompiled) program — benchmarks parse its HLO for bytes."""
    fn, args = make_fct_program(plan, mesh, histogram_backend)
    # fct-lint: waive[R1] -- lowering-only benchmark probe: the program is inspected for HLO stats, never executed warm
    return jax.jit(fn).lower(*args)


# ---------------------------------------------------------------------------
# query runner (deprecated shim — the service API lives in repro/api)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class FCTResult:
    term_ids: np.ndarray
    freqs: np.ndarray
    all_freqs: np.ndarray
    n_cns: int
    n_joined_cns: int
    shuffle_rows: int
    shuffle_bytes: int
    imbalance: float


def run_fct_query(schema: StarSchema, keywords: Sequence[int], *,
                  r_max: int = 4, k_terms: int = 10,
                  mode: str = "uniform", rho: int = 4,
                  sample_frac: float = 1.0, salt: int = 0,
                  mesh: Optional[Mesh] = None,
                  stop_mask: Optional[np.ndarray] = None,
                  histogram_backend: str = "auto",
                  engine=None) -> FCTResult:
    """End-to-end FCT query (Def. 6) over the device mesh.

    .. deprecated::
        Thin shim over :class:`repro.api.FCTSession` — each call builds a
        throwaway session, so tuple sets are re-derived every time.  Callers
        issuing more than one query should hold an ``FCTSession`` (which also
        offers ``query_batch`` and pipelined ``submit``).
    """
    import warnings

    from repro.api import FCTRequest, FCTSession, SessionConfig
    warnings.warn(
        "run_fct_query is deprecated; use repro.api.FCTSession "
        "(query/query_batch/submit)", DeprecationWarning, stacklevel=2)
    session = FCTSession(schema, engine=engine, mesh=mesh,
                         stop_mask=stop_mask,
                         config=SessionConfig(
                             histogram_backend=histogram_backend))
    resp = session.query(FCTRequest(
        keywords=tuple(int(k) for k in keywords), top_k=k_terms, r_max=r_max,
        mode=mode, rho=rho, sample_frac=sample_frac, salt=salt))
    return FCTResult(term_ids=resp.term_ids, freqs=resp.freqs,
                     all_freqs=resp.all_freqs, n_cns=resp.n_cns,
                     n_joined_cns=resp.n_joined_cns,
                     shuffle_rows=resp.shuffle_rows,
                     shuffle_bytes=resp.shuffle_bytes,
                     imbalance=resp.imbalance)
