"""Layer 2: jaxpr contract checker for the four FCT program families.

The AST lint (layer 1) polices *source* invariants; this module checks the
invariants that only exist in the *lowered program*.  It traces the exact
shard_map programs the runtime engine dispatches — ``fct_batched`` /
``fct_batched_percn`` (host-stacked relations), ``fct_store`` /
``fct_store_percn`` (device-resident columns) and the ``fct_topk``
finalize family (on-device top-k over the aggregated histogram) — over
abstract ``ShapeDtypeStruct`` arguments for representative
``PlanSignature`` buckets, and asserts on the closed jaxpr:

C1 (collective census)
    Exactly ONE cross-device reduction collective per dispatch: a
    vocab-sharded ``reduce_scatter`` on multi-device meshes, a ``psum`` at
    P=1.  On multi-device meshes the routing stage contributes exactly
    ``3 * (1 + m)`` ``all_to_all``\\ s (text/keys/mask per relation) and
    nothing else moves data across devices; at P=1 it routes in place and
    contributes none.  A second reduction collective means someone
    re-aggregated an already-aggregated histogram — double traffic and,
    under psum_scatter, wrong totals.

C2 (integer closure)
    No floating-point value anywhere in the program.  The paper's MR² is
    pure integer counting and PR 5 made the whole device path integer-exact
    (split-limb pallas kernel included); a single f32 intermediate
    reintroduces silent rounding exactly where the AccumPolicy promises
    exactness.

C3 (transfer budget)
    The program's output is the histogram and nothing else, and its global
    element count matches the aggregation layout: ``vocab_padded(vocab, P)``
    vocab-sharded elements under reduce-scatter (each device owns
    ``vocab/P`` bins — the O(vocab/P) per-device transfer the scale-out PR
    is built on), exactly ``vocab`` replicated elements under psum, with a
    leading ``n_stack`` axis for the per-CN families.

C4 (bucketing)
    Every data-dependent input dim (rows, send capacity, text width, key
    domain) is a power of two no smaller than ``BUCKET_MIN``, and the
    per-CN families' stack axis is one the engine's ``cn_stack`` rule
    yields (1, 2 or a multiple of ``CN_BUCKET_MIN``) — the shape lattice
    that makes the executable cache finite.

``check_all_contracts()`` runs every family under every *available* policy
(int64-exact needs ``jax_enable_x64``; the x64 CI job covers it) on the
process mesh and returns human-readable failure strings — empty means the
contracts hold.  Corrupting the program (float accumulator, second psum)
must flip it red: ``tests/test_analysis.py`` does exactly that.
"""
from __future__ import annotations

from typing import Iterator, List, Optional, Sequence, Tuple

from repro.core.accum import INT32_CHECKED, INT64_EXACT, AccumPolicy
from repro.runtime.batch import BUCKET_MIN, PlanSignature, RelationSig, x64_flag

#: reduction collectives C1 counts (jaxpr primitive names)
REDUCTION_PRIMITIVES = ("psum", "reduce_scatter", "psum_scatter")
#: every primitive that moves data across mesh devices
COLLECTIVE_PRIMITIVES = REDUCTION_PRIMITIVES + (
    "all_to_all", "all_gather", "ppermute", "pgather")

KINDS = ("fct_batched", "fct_batched_percn", "fct_store", "fct_store_percn")


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------

def iter_eqns(jaxpr) -> Iterator:
    """Every equation of a (closed) jaxpr, recursing into sub-jaxprs carried
    in params (shard_map/pjit bodies, scan/cond branches, custom calls)."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)  # ClosedJaxpr -> Jaxpr
    for eqn in inner.eqns:
        yield eqn
        for value in eqn.params.values():
            values = value if isinstance(value, (list, tuple)) else (value,)
            for v in values:
                if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
                    yield from iter_eqns(v)


def count_primitives(jaxpr, names: Sequence[str]) -> dict:
    counts = {n: 0 for n in names}
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name in counts:
            counts[eqn.primitive.name] += 1
    return counts


def float_avals(jaxpr) -> List[str]:
    """Descriptions of every floating-point value in the program (inputs,
    equation outputs, anywhere) — the integer-closure contract C2 requires
    this to be empty."""
    import jax.numpy as jnp
    bad: List[str] = []
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for var in inner.invars:
        aval = var.aval
        if jnp.issubdtype(aval.dtype, jnp.floating):
            bad.append(f"input {aval.str_short()}")
    for eqn in iter_eqns(jaxpr):
        for var in eqn.outvars:
            aval = var.aval
            if hasattr(aval, "dtype") and jnp.issubdtype(aval.dtype,
                                                         jnp.floating):
                bad.append(f"{eqn.primitive.name} -> {aval.str_short()}")
    return bad


# ---------------------------------------------------------------------------
# representative signatures and abstract arguments
# ---------------------------------------------------------------------------

def representative_signatures(n_devices: int,
                              policies: Sequence[AccumPolicy]
                              ) -> List[PlanSignature]:
    """One small and one wide bucket per policy.

    The small bucket's vocab (100) is deliberately NOT a multiple of P>1 so
    the reduce-scatter vocab pad is exercised; the wide one (512) divides
    any pow-2 P evenly.  m=1 and m=2 cover the single- and multi-dimension
    routing shapes; ``key_width=2`` makes the store path's on-device
    column gather non-trivial.
    """
    sigs = []
    for accum in policies:
        sigs.append(PlanSignature(
            n_devices=n_devices, vocab=100,
            fact=RelationSig(rows=16, cap=8, text_len=8, key_width=2),
            dims=(RelationSig(rows=8, cap=8, text_len=8, domain=8),),
            accum=accum))
        sigs.append(PlanSignature(
            n_devices=n_devices, vocab=512,
            fact=RelationSig(rows=32, cap=16, text_len=16, key_width=2),
            dims=(RelationSig(rows=16, cap=8, text_len=8, domain=16),
                  RelationSig(rows=8, cap=8, text_len=8, domain=8)),
            accum=accum))
    return sigs


def _sds(shape, dtype=None):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct(shape, dtype or jnp.int32)


def batched_abstract_args(sig: PlanSignature, n_stack: int):
    """ShapeDtypeStruct pytree matching ``stack_group``'s [N, P, ...] output
    (the host-stacked families' global arguments; columns lane-major)."""
    p = sig.n_devices

    def rel(rsig: RelationSig, key_tail: Tuple[int, ...]):
        return {"text": _sds((n_stack, p, rsig.text_len, rsig.rows)),
                "keys": _sds((n_stack, p) + key_tail + (rsig.rows,)),
                "send": _sds((n_stack, p, p, rsig.cap))}

    fact = rel(sig.fact, (sig.m,))
    dims = [rel(r, ()) for r in sig.dims]
    return fact, dims


def store_abstract_args(sig: PlanSignature, n_stack: int):
    """ShapeDtypeStruct pytree matching ``store_group_args``: per relation,
    ``n_stack`` device-resident lane-major [P, ..., S] column arrays plus the stacked
    host send tables; the fact adds its per-CN key-column indices."""
    p = sig.n_devices

    def rel(rsig: RelationSig, key_tail: Tuple[int, ...]):
        return {"text": [_sds((p, rsig.text_len, rsig.rows))] * n_stack,
                "keys": [_sds((p,) + key_tail + (rsig.rows,))] * n_stack,
                "send": _sds((n_stack, p, p, rsig.cap))}

    fact = rel(sig.fact, (sig.fact.key_width,))
    fact["cols"] = _sds((n_stack, sig.m))
    dims = [rel(r, ()) for r in sig.dims]
    return fact, dims


def trace_family(kind: str, sig: PlanSignature, n_stack: int, mesh,
                 histogram_backend: str = "ref"):
    """The closed jaxpr of one engine program family, traced exactly as the
    engine builds it (same builders, same specs), over abstract args."""
    import jax

    from repro.runtime.engine import _build_batched_fn, _build_store_fn

    reduce_cns = not kind.endswith("percn")
    # mirrors FCTEngine._dispatch: reduce-scatter only pays on real meshes
    rs = sig.n_devices > 1
    if kind.startswith("fct_store"):
        fn = _build_store_fn(sig, mesh, histogram_backend, n_stack,
                             reduce_cns=reduce_cns, reduce_scatter=rs)
        args = store_abstract_args(sig, n_stack)
    else:
        fn = _build_batched_fn(sig, mesh, histogram_backend,
                               reduce_cns=reduce_cns, reduce_scatter=rs)
        args = batched_abstract_args(sig, n_stack)
    return jax.make_jaxpr(fn)(*args)


# ---------------------------------------------------------------------------
# the contracts
# ---------------------------------------------------------------------------

def _is_pow2(n: int) -> bool:
    return n >= 1 and (n & (n - 1)) == 0


def check_contract(kind: str, sig: PlanSignature, n_stack: int, mesh,
                   histogram_backend: str = "ref") -> List[str]:
    """Check C1-C4 for one (family, signature) pair; returns failure strings
    prefixed ``kind[vocab=..,m=..,policy]``."""
    from repro.runtime.engine import cn_stack, vocab_padded

    tag = (f"{kind}[P={sig.n_devices},vocab={sig.vocab},m={sig.m},"
           f"{sig.accum.name}]")
    failures: List[str] = []
    reduce_cns = not kind.endswith("percn")
    rs = sig.n_devices > 1

    # C4 first — a malformed signature makes the other checks meaningless
    for label, rsig in [("fact", sig.fact)] + [
            (f"dim{i}", r) for i, r in enumerate(sig.dims)]:
        for dim_name, value in (("rows", rsig.rows), ("cap", rsig.cap),
                                ("text_len", rsig.text_len)):
            if not (_is_pow2(value) and value >= BUCKET_MIN):
                failures.append(
                    f"{tag} C4: {label}.{dim_name}={value} is not a power "
                    f"of two >= BUCKET_MIN={BUCKET_MIN} (signature escaped "
                    f"bucket_pow2)")
        if rsig.domain and not _is_pow2(rsig.domain):
            failures.append(
                f"{tag} C4: {label}.domain={rsig.domain} is not a power of "
                f"two (signature escaped bucket_pow2)")
    if not reduce_cns and cn_stack(n_stack) != n_stack:
        failures.append(
            f"{tag} C4: per-CN stack axis n_stack={n_stack} is not a "
            f"cn_stack size (1, 2 or a multiple of CN_BUCKET_MIN) — every "
            f"window composition compiles a fresh program variant")
    if failures:
        return failures

    try:
        jaxpr = trace_family(kind, sig, n_stack, mesh, histogram_backend)
    except Exception as exc:  # a family that cannot trace is a failure too
        return [f"{tag} trace failed: {type(exc).__name__}: {exc}"]

    # C1: collective census
    counts = count_primitives(jaxpr, COLLECTIVE_PRIMITIVES)
    reductions = sum(counts[n] for n in REDUCTION_PRIMITIVES)
    expected = "reduce_scatter" if rs else "psum"
    if reductions != 1:
        got = {n: c for n, c in counts.items()
               if c and n in REDUCTION_PRIMITIVES}
        failures.append(
            f"{tag} C1: {reductions} reduction collectives ({got}), "
            f"expected exactly one {expected} — a second aggregation "
            f"doubles cross-device traffic and double-counts under "
            f"psum_scatter")
    elif counts[expected] != 1:
        got = next(n for n in REDUCTION_PRIMITIVES if counts[n])
        failures.append(
            f"{tag} C1: aggregation uses {got}, expected {expected} "
            f"at P={sig.n_devices}")
    n_a2a = 3 * (1 + sig.m) if sig.n_devices > 1 else 0
    if counts["all_to_all"] != n_a2a:
        failures.append(
            f"{tag} C1: {counts['all_to_all']} all_to_alls, expected "
            f"{n_a2a} (text/keys/mask per relation, none at P=1) — the "
            f"routing stage grew extra shuffles")
    extras = {n: c for n, c in counts.items()
              if c and n not in REDUCTION_PRIMITIVES + ("all_to_all",)}
    if extras:
        failures.append(f"{tag} C1: unexpected collectives {extras}")

    # C2: integer closure
    floats = float_avals(jaxpr)
    if floats:
        failures.append(
            f"{tag} C2: {len(floats)} floating-point value(s) in an "
            f"integer-exact program (first: {floats[0]}) — the "
            f"{sig.accum.name} policy promises exact counts")

    # C3: transfer budget
    out_avals = jaxpr.out_avals
    if len(out_avals) != 1:
        failures.append(f"{tag} C3: {len(out_avals)} outputs, expected the "
                        f"histogram alone")
    else:
        vp = vocab_padded(sig.vocab, sig.n_devices)
        vocab_axis = vp if rs else sig.vocab
        want = (vocab_axis,) if reduce_cns else (n_stack, vocab_axis)
        got = tuple(out_avals[0].shape)
        if got != want:
            failures.append(
                f"{tag} C3: output shape {got}, expected {want} "
                f"({'vocab-sharded, O(vocab/P) per device' if rs else 'replicated vocab'})")
        if out_avals[0].dtype != sig.accum.dtype:
            failures.append(
                f"{tag} C3: output dtype {out_avals[0].dtype} does not "
                f"advertise the accumulation policy ({sig.accum.name} -> "
                f"{sig.accum.dtype.__name__})")
    return failures


def check_topk_contract(sig: PlanSignature, mesh,
                        kw_pad: Optional[int] = None) -> List[str]:
    """C1-C4 variant for the ``fct_topk`` finalize family.

    The family's whole reason to exist is C3': its outputs are O(k), not
    O(vocab/P) — ``k_eff`` counts in the policy dtype, ``k_eff`` int32 term
    ids and one int32 overflow flag, ``2 * k_eff + 1`` elements total.  C1'
    pins the merge topology: under reduce-scatter exactly THREE
    ``all_gather``\\ s over the small k axis (values / ids / wrap flags) and
    no reduction collective — a ``psum`` here would re-aggregate an
    already-aggregated histogram; on replicated inputs (P=1 / psum mode)
    zero collectives, since gathering replicated candidates would duplicate
    each term P times.  C2 (integer closure) and C4 (pow-2 ``k_bucket``,
    floor ``TOPK_BUCKET_MIN``) carry over unchanged.
    """
    import jax
    import jax.numpy as jnp

    from repro.runtime.engine import (KW_BUCKET_MIN, TOPK_BUCKET_MIN,
                                      _build_topk_fn, k_effective,
                                      vocab_padded)

    rs = sig.n_devices > 1
    if kw_pad is None:
        kw_pad = KW_BUCKET_MIN
    tag = (f"fct_topk[P={sig.n_devices},vocab={sig.vocab},"
           f"k_bucket={sig.k_bucket},{sig.accum.name}]")
    failures: List[str] = []

    # C4: the k axis must ride the same bucket lattice as every other
    # data-dependent dim, or the executable cache grows per distinct k
    if not (_is_pow2(sig.k_bucket) and sig.k_bucket >= TOPK_BUCKET_MIN):
        failures.append(
            f"{tag} C4: k_bucket={sig.k_bucket} is not a power of two >= "
            f"TOPK_BUCKET_MIN={TOPK_BUCKET_MIN} (signature escaped "
            f"bucket_pow2)")
    if not (_is_pow2(kw_pad) and kw_pad >= KW_BUCKET_MIN):
        failures.append(
            f"{tag} C4: kw_pad={kw_pad} is not a power of two >= "
            f"KW_BUCKET_MIN={KW_BUCKET_MIN}")
    if failures:
        return failures

    vp = vocab_padded(sig.vocab, sig.n_devices) if rs else sig.vocab
    k_eff = k_effective(sig)
    hist = _sds((vp,), sig.accum.dtype)
    kw = _sds((kw_pad,), jnp.int32)
    excl = _sds((vp,), jnp.int8)
    try:
        jaxpr = jax.make_jaxpr(_build_topk_fn(sig, mesh, rs, kw_pad))(
            hist, kw, excl)
    except Exception as exc:
        return [f"{tag} trace failed: {type(exc).__name__}: {exc}"]

    # C1': merge topology
    counts = count_primitives(jaxpr, COLLECTIVE_PRIMITIVES)
    reductions = sum(counts[n] for n in REDUCTION_PRIMITIVES)
    if reductions:
        got = {n: c for n, c in counts.items()
               if c and n in REDUCTION_PRIMITIVES}
        failures.append(
            f"{tag} C1: {reductions} reduction collectives ({got}) in the "
            f"finalize program — the histogram is already aggregated; a "
            f"second reduction double-counts")
    want_gathers = 3 if rs else 0
    if counts["all_gather"] != want_gathers:
        failures.append(
            f"{tag} C1: {counts['all_gather']} all_gathers, expected "
            f"{want_gathers} (values/ids/wrap over the k axis"
            f"{'' if rs else '; replicated inputs need none'})")
    extras = {n: c for n, c in counts.items()
              if c and n not in REDUCTION_PRIMITIVES + ("all_gather",)}
    if extras:
        failures.append(f"{tag} C1: unexpected collectives {extras}")

    # C2: integer closure
    floats = float_avals(jaxpr)
    if floats:
        failures.append(
            f"{tag} C2: {len(floats)} floating-point value(s) in an "
            f"integer-exact program (first: {floats[0]})")

    # C3': O(k) transfer budget
    out_avals = jaxpr.out_avals
    want_shapes = ((k_eff,), (k_eff,), ())
    got_shapes = tuple(tuple(a.shape) for a in out_avals)
    if got_shapes != want_shapes:
        failures.append(
            f"{tag} C3: output shapes {got_shapes}, expected {want_shapes} "
            f"(counts[k_eff], ids[k_eff], wrap flag)")
    else:
        total = sum(int(a.size) for a in out_avals)
        if total != 2 * k_eff + 1:
            failures.append(
                f"{tag} C3: {total} output elements, expected "
                f"{2 * k_eff + 1} — the device->host transfer must stay "
                f"O(k), not O(vocab/P)")
        if out_avals[0].dtype != sig.accum.dtype:
            failures.append(
                f"{tag} C3: counts dtype {out_avals[0].dtype} does not "
                f"advertise the accumulation policy ({sig.accum.name} -> "
                f"{sig.accum.dtype.__name__})")
        if any(a.dtype != jnp.int32 for a in out_avals[1:]):
            failures.append(
                f"{tag} C3: ids/wrap dtypes "
                f"{[str(a.dtype) for a in out_avals[1:]]}, expected int32")
    return failures


def check_all_contracts(mesh=None,
                        policies: Optional[Sequence[AccumPolicy]] = None,
                        histogram_backend: str = "ref"
                        ) -> Tuple[List[str], int]:
    """Run C1-C4 for all four families over the representative signature
    buckets; returns (failures, programs_checked).

    ``policies`` defaults to every policy the process can trace:
    INT32_CHECKED always, INT64_EXACT when ``jax_enable_x64`` is on (the
    x64 CI job runs both).  ``mesh`` defaults to all process devices —
    under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` this
    checks the P=8 programs the multidevice CI job ships.
    """
    from repro.launch.mesh import make_worker_mesh
    from repro.runtime.engine import CN_BUCKET_MIN

    if mesh is None:
        mesh = make_worker_mesh()
    if policies is None:
        policies = [INT32_CHECKED] + ([INT64_EXACT] if x64_flag() else [])
    n_devices = mesh.devices.size
    failures: List[str] = []
    checked = 0
    for sig in representative_signatures(n_devices, policies):
        for kind in KINDS:
            n_stack = 2 if not kind.endswith("percn") else CN_BUCKET_MIN
            failures.extend(check_contract(kind, sig, n_stack, mesh,
                                           histogram_backend))
            checked += 1
    # the fct_topk finalize family, over the same two vocab buckets (100
    # exercises the reduce-scatter vocab pad at P>1, 512 divides evenly)
    from repro.runtime.engine import topk_signature
    for accum in policies:
        for vocab in (100, 512):
            tsig = topk_signature(vocab, n_devices, accum, k=10)
            failures.extend(check_topk_contract(tsig, mesh))
            checked += 1
    return failures, checked
