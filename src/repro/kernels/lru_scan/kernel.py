"""Pallas TPU kernel for the RG-LRU diagonal recurrence.

Hardware adaptation (see ``src/repro/kernels/README.md``): GPU
implementations (and the Griffin
paper's TPU note) favour parallel prefix scans; on TPU the VPU is wide
enough that the right layout is *sequential in time, vector-parallel in
channels*: grid (B, channel_blocks, seq_blocks) with the carry h [wb] held
in VMEM scratch across the sequential seq_blocks sweep.  One pass over HBM
(read a,b once, write h once) — the associative scan's log(S) passes become
1, which is why the memory-bound recurrentgemma cells hillclimb with this
kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


def _kernel(a_ref, b_ref, o_ref, h_scr, *, block_s: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    def step(t, h):
        # unit dims indexed with dslice, not bare ints: the interpret-mode
        # discharge rule only accepts Slice/array indices
        idx = (pl.dslice(0, 1), pl.dslice(t, 1), slice(None))
        h = a_ref[idx][0, 0] * h + b_ref[idx][0, 0]
        o_ref[idx] = h[None, None]
        return h

    h_scr[...] = lax.fori_loop(0, block_s, step, h_scr[...])


@functools.partial(jax.jit, static_argnames=("block_s", "block_w",
                                             "interpret"))
def lru_scan_pallas(a: jnp.ndarray, b: jnp.ndarray, block_s: int = 256,
                    block_w: int = 512, interpret: bool = False):
    """a, b [B, S, W] -> h [B, S, W] with h_t = a_t·h_{t-1} + b_t."""
    from jax.experimental.pallas import tpu as pltpu

    bsz, s, w = a.shape
    bs = min(block_s, s)
    bw = min(block_w, w)
    pad_s = (-s) % bs
    pad_w = (-w) % bw
    if pad_s or pad_w:
        cfgp = ((0, 0), (0, pad_s), (0, pad_w))
        a = jnp.pad(a, cfgp)
        b = jnp.pad(b, cfgp)
    ns, nw = a.shape[1] // bs, a.shape[2] // bw
    out = pl.pallas_call(
        functools.partial(_kernel, block_s=bs),
        grid=(bsz, nw, ns),                       # seq innermost: carry flows
        in_specs=[
            pl.BlockSpec((1, bs, bw), lambda bi, wi, si: (bi, si, wi)),
            pl.BlockSpec((1, bs, bw), lambda bi, wi, si: (bi, si, wi)),
        ],
        out_specs=pl.BlockSpec((1, bs, bw), lambda bi, wi, si: (bi, si, wi)),
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        scratch_shapes=[pltpu.VMEM((bw,), jnp.float32)],
        interpret=interpret,
    )(a, b)
    return out[:, :s, :w]
