"""Every op of the engine's programs names the device-body stage it belongs
to, and every program is named after its kind.

The programs are built through an ``ExecutableCache`` exactly as the engine
keys them, compiled on the CPU at small shapes, and read back from the
compiled HLO text: the innermost ``fct.<stage>`` scope of an op's
``op_name`` is the stage a profile attributes its device time to.  P=4 runs
in a subprocess (the host device count is fixed when jax starts).
"""
import json
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

STAGES = ("stack", "route", "mr1", "mr2", "reduce", "topk", "collective")
KINDS = ("fct_store", "fct_store_percn", "fct_batched", "fct_topk")
#: opcodes that must name their stage, and those that move data across
#: devices (which must name ``fct.collective``)
CHECKED = ("gather", "scatter", "custom-call", "all-to-all", "all-reduce",
           "reduce-scatter", "all-gather")
COLLECTIVES = ("all-to-all", "all-reduce", "reduce-scatter", "all-gather")
_OP = re.compile(r" = .*?\s(" + "|".join(re.escape(c) for c in CHECKED)
                 + r")(?:-start)?\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"\bfct\.(\w+)")
_REPO = Path(__file__).resolve().parents[1]


def census(n_devices: int) -> dict:
    """Per kind: the compiled module's name and (opcode, op_name) of every
    checked op, on a mesh of the first ``n_devices`` devices."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from repro.analysis.contracts import (batched_abstract_args,
                                          representative_signatures,
                                          store_abstract_args)
    from repro.core.accum import INT32_CHECKED
    from repro.runtime.cache import ExecutableCache
    from repro.runtime.engine import (KW_BUCKET_MIN, _build_batched_fn,
                                      _build_store_fn, _build_topk_fn,
                                      topk_signature, vocab_padded)

    mesh = Mesh(np.array(jax.devices()[:n_devices]), ("w",))
    sig = representative_signatures(n_devices, [INT32_CHECKED])[1]
    rs = n_devices > 1
    cache = ExecutableCache()
    n_stack = 4
    tsig = topk_signature(sig.vocab, n_devices, sig.accum, 10)
    vp = vocab_padded(sig.vocab, n_devices) if rs else sig.vocab
    builds = {
        "fct_store": (lambda: _build_store_fn(sig, mesh, "ref", n_stack,
                                              reduce_scatter=rs),
                      store_abstract_args(sig, n_stack)),
        "fct_store_percn": (lambda: _build_store_fn(
            sig, mesh, "ref", n_stack, reduce_cns=False, reduce_scatter=rs),
            store_abstract_args(sig, n_stack)),
        "fct_batched": (lambda: _build_batched_fn(sig, mesh, "ref",
                                                  reduce_scatter=rs),
                        batched_abstract_args(sig, n_stack)),
        "fct_topk": (lambda: _build_topk_fn(tsig, mesh, rs, KW_BUCKET_MIN),
                     (jax.ShapeDtypeStruct((vp,), sig.accum.dtype),
                      jax.ShapeDtypeStruct((KW_BUCKET_MIN,), jnp.int32),
                      jax.ShapeDtypeStruct((vp,), jnp.int8))),
    }
    out = {}
    for kind in KINDS:
        builder, args = builds[kind]
        fn = cache.get_or_build((kind, sig, n_stack, mesh), builder)
        text = fn.lower(*args).compile().as_text()
        ops = []
        for line in text.splitlines():
            op = _OP.search(line)
            if op:
                name = _OP_NAME.search(line)
                ops.append((op.group(1), name.group(1) if name else ""))
        out[kind] = {"module": re.search(r"HloModule (\S+?),",
                                         text).group(1), "ops": ops}
    return out


def _innermost(op_name: str):
    scopes = _SCOPE.findall(op_name)
    return scopes[-1] if scopes else None


def _check(result: dict, n_devices: int) -> None:
    for kind in KINDS:
        got = result[kind]
        assert got["module"] == f"jit_{kind}", got["module"]
        assert got["ops"], kind
        for opcode, op_name in got["ops"]:
            scopes = _SCOPE.findall(op_name)
            assert scopes and set(scopes) <= set(STAGES), (kind, opcode,
                                                           op_name)
            if opcode in COLLECTIVES:
                assert _innermost(op_name) == "collective", (kind, op_name)
        stages = {_innermost(n) for _, n in got["ops"]}
        if kind == "fct_topk":
            assert stages <= {"topk", "collective"}, stages
        elif n_devices > 1:
            assert {"route", "mr1", "mr2"} <= stages, (kind, stages)
        else:
            # one device routes in place: nothing is gathered or exchanged
            # under fct.route
            assert {"mr1", "mr2"} <= stages, (kind, stages)
            routed = [(op, n) for op, n in got["ops"]
                      if op in ("gather", "all-to-all")
                      and _innermost(n) == "route"]
            assert not routed, (kind, routed)
        if n_devices > 1:
            opcodes = {op for op, _ in got["ops"]}
            want = {"all-gather"} if kind == "fct_topk" else {"all-to-all"}
            assert want <= opcodes, (kind, opcodes)


def test_stage_scopes_and_program_names_one_device():
    _check(census(1), 1)


def test_stage_scopes_and_program_names_four_devices():
    script = textwrap.dedent(f"""
        import os, sys, json
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, {str(Path(__file__).parent)!r})
        from test_device_scopes import census
        print("RESULT" + json.dumps(census(4)))
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_ENABLE_X64", None)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=_REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT")][0]
    result = json.loads(line[len("RESULT"):])
    _check(result, 4)
    # the store family's collectives: the route's all_to_alls and one
    # reduction (a reduce-scatter, or the all-reduce it lowers to)
    ops = {op for op, _ in result["fct_store"]["ops"]}
    assert ops & {"reduce-scatter", "all-reduce"}, ops

