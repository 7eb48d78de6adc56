"""Compile-only checks of the FCT serving programs for a described TPU v5e.

Nothing here runs on a chip.  The TPU compiler is installed beside the CPU
backend, and it compiles for a topology that is described, not attached, so
these tests catch what interpret mode cannot: kernel layouts Mosaic refuses,
kernels whose scoped VMEM (as the compiled custom call reports it) exceeds
the core's default limit, and programs that do not fit the chip's HBM.  Shapes are TPC-H SF1 sized (LINEITEM 6M rows bucketed
to 2^23, ``text_len`` 8, vocab 4096); no array is ever allocated.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.  All tests stay in this one file so a single worker owns
the library.
"""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core.accum import INT32_CHECKED
from repro.kernels.fct_count.kernel import fct_count_pallas_exact
from repro.runtime.batch import PlanSignature, RelationSig
from repro.runtime.engine import _build_store_fn

HBM_BYTES = 16 * 10**9          # one TPU v5e chip
SCOPED_VMEM_BYTES = 16 << 20    # v5e default scoped-VMEM limit per kernel
_VMEM_SPACE = "1"               # Mosaic's memory-space id of VMEM
SF1_FACT_ROWS = 8_388_608       # 6,000,000 LINEITEM rows, pow-2 bucketed
SF1_TEXT_LEN = 8
SF1_VOCAB = 4096

#: one signature group of an SF1 query on one chip (3 keywords planted in
#: PART / SUPPLIER / ORDERS at 8%, r_max=4): the whole fact relation joined
#: with three keyword tuple sets.  Two CNs share it.
SF1_SIG_1CHIP = PlanSignature(
    n_devices=1, vocab=SF1_VOCAB,
    fact=RelationSig(rows=SF1_FACT_ROWS, cap=SF1_FACT_ROWS,
                     text_len=SF1_TEXT_LEN, key_width=3),
    dims=(RelationSig(rows=32, cap=32, text_len=SF1_TEXT_LEN,
                      domain=262_144),
          RelationSig(rows=8, cap=8, text_len=SF1_TEXT_LEN, domain=16_384),
          RelationSig(rows=256, cap=256, text_len=SF1_TEXT_LEN,
                      domain=2_097_152)),
    accum=INT32_CHECKED)

#: one signature group of an SF1 query on a 2x2 mesh: a quarter of the
#: fact rows per device, a quarter of those per destination, four CNs
SF1_SIG_4CHIP = PlanSignature(
    n_devices=4, vocab=SF1_VOCAB,
    fact=RelationSig(rows=SF1_FACT_ROWS // 4, cap=SF1_FACT_ROWS // 16,
                     text_len=SF1_TEXT_LEN, key_width=3),
    dims=(RelationSig(rows=8, cap=8, text_len=SF1_TEXT_LEN, domain=262_144),
          RelationSig(rows=8, cap=8, text_len=SF1_TEXT_LEN,
                      domain=2_097_152)),
    accum=INT32_CHECKED)

#: the star query's group on the same mesh: the fact joined with all three
#: dimensions, so three dims are routed and shuffled
SF1_STAR_SIG_4CHIP = PlanSignature(
    n_devices=4, vocab=SF1_VOCAB,
    fact=RelationSig(rows=SF1_FACT_ROWS // 4, cap=SF1_FACT_ROWS // 16,
                     text_len=SF1_TEXT_LEN, key_width=3),
    dims=(RelationSig(rows=8, cap=8, text_len=SF1_TEXT_LEN, domain=262_144),
          RelationSig(rows=8, cap=8, text_len=SF1_TEXT_LEN, domain=16_384),
          RelationSig(rows=64, cap=64, text_len=SF1_TEXT_LEN,
                      domain=2_097_152)),
    accum=INT32_CHECKED)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these tests
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot"
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _store_args(sig: PlanSignature, mesh: Mesh, n_stack: int):
    """Shape-only arguments of the ``fct_store`` family, laid out as the
    RelationStore and ``store_group_args`` lay them out (lane-major
    columns: rows on the last axis)."""
    col = NamedSharding(mesh, P("w"))
    send = NamedSharding(mesh, P(None, "w"))
    n_dev = sig.n_devices

    def rel(rsig: RelationSig, key_mid):
        return {
            "text": [jax.ShapeDtypeStruct((n_dev, rsig.text_len, rsig.rows),
                                          jnp.int32, sharding=col)] * n_stack,
            "keys": [jax.ShapeDtypeStruct((n_dev,) + key_mid + (rsig.rows,),
                                          jnp.int32, sharding=col)] * n_stack,
            "send": jax.ShapeDtypeStruct((n_stack, n_dev, n_dev, rsig.cap),
                                         jnp.int32, sharding=send)}

    fact = rel(sig.fact, (sig.fact.key_width,))
    fact["cols"] = jax.ShapeDtypeStruct((n_stack, sig.m), jnp.int32,
                                        sharding=NamedSharding(mesh, P()))
    return fact, [rel(d, ()) for d in sig.dims]


def _lower_store(sig: PlanSignature, mesh: Mesh, n_stack: int,
                 reduce_scatter: bool):
    fn = _build_store_fn(sig, mesh, "pallas", n_stack,
                         reduce_scatter=reduce_scatter)
    return jax.jit(fn).lower(*_store_args(sig, mesh, n_stack))


def _scoped_vmem_bytes(compiled) -> int:
    """VMEM the Mosaic kernels of ``compiled`` reserve, as each
    ``tpu_custom_call``'s backend config reports it."""
    total = 0
    for line in compiled.as_text().splitlines():
        if "tpu_custom_call" not in line:
            continue
        m = re.search(r'"used_scoped_memory_configs":(\[.*?\])', line)
        assert m, "custom call reports no scoped memory"
        total += sum(int(c["size"]) for c in json.loads(m.group(1))
                     if c["memory_space"] == _VMEM_SPACE)
    return total


def _device_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


@pytest.mark.parametrize("text_len", [8, 12])
@pytest.mark.parametrize("vocab", [2048, 4096])
def test_fct_count_exact_compiles_for_v5e(topo, vocab, text_len):
    one_chip = SingleDeviceSharding(topo.devices[0])
    tokens = jax.ShapeDtypeStruct((text_len, SF1_FACT_ROWS), jnp.int32,
                                  sharding=one_chip)       # lane-major
    weights = jax.ShapeDtypeStruct((SF1_FACT_ROWS,), jnp.int32,
                                   sharding=one_chip)
    compiled = fct_count_pallas_exact.lower(tokens, weights, vocab).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert 0 < _scoped_vmem_bytes(compiled) < SCOPED_VMEM_BYTES


def test_fct_store_program_fits_one_v5e(topo):
    mesh = Mesh(np.array(topo.devices[:1]), ("w",))
    compiled = _lower_store(SF1_SIG_1CHIP, mesh, n_stack=2,
                            reduce_scatter=False).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text       # the Mosaic kernel, not the ref
    assert _device_bytes(compiled) < HBM_BYTES


def _check_sharded_store_program(topo, sig: PlanSignature):
    mesh = Mesh(np.array(topo.devices), ("w",))
    lowered = _lower_store(sig, mesh, n_stack=4, reduce_scatter=True)
    # the program asks for a vocab-sharded MR2 aggregation; at 4096 bins the
    # TPU compiler may carry it out as an all-reduce plus a slice, so the
    # reduce-scatter is checked where the program states it
    hlo = lowered.as_text(dialect="hlo")
    assert "reduce-scatter" in hlo
    # MR1 routes text, keys and send counts of the fact and of every dim
    assert hlo.count("all-to-all(") == 3 * (1 + sig.m)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-to-all" in text            # MR1 shuffle
    assert _device_bytes(compiled) < HBM_BYTES


def test_fct_store_program_shards_over_v5e_2x2(topo):
    _check_sharded_store_program(topo, SF1_SIG_4CHIP)


def test_fct_store_star_program_shards_over_v5e_2x2(topo):
    _check_sharded_store_program(topo, SF1_STAR_SIG_4CHIP)


_STAGE = re.compile(r"\bfct\.(stack|route|mr1|mr2|reduce|topk|collective)\b")
_CALLED = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")


def _entry_op_stages(text: str) -> dict:
    """Per instruction of a compiled module's entry computation: its opcode
    (a fusion's kind), the innermost ``fct.*`` stage of its own
    ``op_name`` (None without one), and the stages named inside the
    computations it calls, transitively."""
    comps, cur, entry = {}, None, None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(", line)
        if head:
            cur = head.group(2)
            comps[cur] = []
            entry = cur if head.group(1) else entry
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            comps[cur].append(line)

    def inner(comp, seen):
        out = set()
        for line in comps.get(comp, []):
            out.update(_STAGE.findall(line))
            for callee in _CALLED.findall(line):
                if callee not in seen:
                    seen.add(callee)
                    out |= inner(callee, seen)
        return out

    ops = {}
    for line in comps[entry]:
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = .*?\s([\w-]+)\(", line)
        if not m:
            continue
        name_ = re.search(r'op_name="([^"]*)"', line)
        own = _STAGE.findall(name_.group(1)) if name_ else []
        kind = re.search(r"kind=(k\w+)", line)
        ops[m.group(1)] = {
            "opcode": kind.group(1) if kind else m.group(2),
            "own": own[-1] if own else None,
            "inner": set().union(*[inner(c, {c}) for c in
                                   _CALLED.findall(line)] or [set()]),
            "operands": re.findall(r"%([\w.\-]+)", line.split("=", 1)[1])}
    return ops


def _downstream_stages(ops: dict, name: str) -> set:
    """The stages of the nearest ops fed by ``name`` that name one, looking
    through those that do not (tuple elements, bitcasts, copies)."""
    out, todo, seen = set(), [name], {name}
    while todo:
        src = todo.pop()
        for user, op in ops.items():
            if src in op["operands"] and user not in seen:
                seen.add(user)
                stages = {op["own"]} if op["own"] else op["inner"]
                if stages:
                    out |= stages
                else:
                    todo.append(user)
    return out


@pytest.mark.parametrize("n_dev", [1, 4])
def test_unnamed_scatter_ops_name_mr1_inside(topo, n_dev):
    """The TPU compiler turns the MR1 scatter-adds into custom fusions that
    carry no ``op_name`` of their own, so a trace leaves them unstaged.
    Their fused computations still name the stage: every such op of the
    per-CN program is MR1's.  At the cell's size (2^25 routed rows) the
    compiler also puts a sort, likewise unnamed, before the largest; these
    shapes make none, and one that appears must feed MR1."""
    fact = 2**16
    sig = PlanSignature(
        n_devices=n_dev, vocab=256,
        fact=RelationSig(rows=fact // n_dev, cap=fact // n_dev // n_dev,
                         text_len=14, key_width=3),
        dims=(RelationSig(rows=512, cap=512, text_len=26, domain=2**15),
              RelationSig(rows=64, cap=64, text_len=7, domain=2**12),
              RelationSig(rows=32, cap=32, text_len=33, domain=2**10)),
        accum=INT32_CHECKED)
    mesh = Mesh(np.array(topo.devices[:n_dev]), ("w",))
    fn = _build_store_fn(sig, mesh, "pallas", 4, reduce_cns=False,
                         reduce_scatter=n_dev > 1)
    text = jax.jit(fn).lower(*_store_args(sig, mesh, 4)).compile().as_text()
    ops = _entry_op_stages(text)
    custom = {n: op for n, op in ops.items()
              if op["opcode"] == "kCustom" and op["own"] is None}
    assert custom
    for name, op in custom.items():
        assert op["inner"] == {"mr1"}, (name, op["inner"])
    for name, op in ops.items():
        if op["opcode"] == "sort" and op["own"] is None:
            assert _downstream_stages(ops, name) == {"mr1"}, name
