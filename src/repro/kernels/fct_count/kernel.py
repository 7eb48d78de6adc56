"""Pallas TPU kernels: weighted token histogram via a count-matrix MXU matmul.

Hardware adaptation (see ``src/repro/kernels/README.md`` for the full
design): the GPU/CPU instinct for a histogram is scatter-add; TPUs have no
fast vector scatter, but the MXU turns the same reduction into a matmul:

    counts[v, r] = #{l : tokens[l, r] == v0 + v}        (VPU, [VB, NB])
    hist[v0:v0+VB] += w[NB] · countsᵀ                    (MXU)

Grid = (vocab_blocks, token_blocks); the token axis is the inner (fastest)
grid dimension, so each vocab tile of the output stays resident in VMEM while
every token block streams through — one output write per vocab tile.

Layout: tokens are LANE-MAJOR, ``[L, N]`` — one row per token position,
rows of the relation along the 128-wide lane axis.  The row-major ``[N, L]``
layout pads its minor dimension (L = 8..16) to 128 lanes in TPU memory, a
8-16x blow-up of every text column in HBM; lane-major text is dense.  Inside
the kernel every block is 2-D and nothing reshapes or transposes across
the lane axis: the count matrix is built in a static loop over the ``L``
token rows, each ``[1, NB]`` row compared against a sublane iota
``[VB, NB]``; weights arrive lane-major too (``[1, NB]`` / limbs
``[K, NB]``), so the contraction over rows is one ``[rows, NB] · [VB, NB]ᵀ``
matmul; the accumulator is written row by row.

Two accumulator schemes share that layout:

``fct_count_pallas`` (float32)
    The weights ride the matmul directly and accumulate in float32 — exact
    only for totals < 2^24.  Kept for floating-point weights.

``fct_count_pallas_exact`` (integer, split-limb int32 accumulators)
    The paper's MR² is pure integer counting, so this is the serving path.
    Each weight is split OUTSIDE the kernel into ``K`` limbs of
    ``limb_bits`` bits (``limb_bits`` chosen so a limb's partial matmul over
    the whole contraction — at most ``NB·L`` token occurrences — stays
    < 2^24 and is therefore exact in float32); inside the kernel one
    ``[K, NB] · [VB, NB]ᵀ`` MXU matmul produces every limb's tile
    contribution at once, which is cast to int32 and added into a
    ``[K, VB]`` int32 accumulator.  After every step the carries are
    propagated (``acc[k] >> limb_bits`` into ``acc[k+1]``), so every non-top
    limb stays < 2^limb_bits and can never wrap; the top limb may wrap, but
    only in multiples of ``2^(32 + limb_bits·(K-1))`` of the recombined
    value, which vanish modulo the output width (ops.py picks
    ``K = ceil(width / limb_bits)``).  The host recombines
    ``Σ acc[k] << (limb_bits·k)`` in the weights' integer dtype — making
    device accumulation bit-identical to an int32/int64 host accumulation,
    wrap-around included.

Exactness of the float32 matmul: counts are integers <= L, exact in
bfloat16, so the ``Precision.HIGHEST`` multi-pass product ``w · count`` is
exact for any float32 ``w`` (every dropped cross term multiplies a zero low
part of the count); partial sums stay exact below 2^24.

VMEM working set per step: L·NB·4 (tokens) + K·NB·4 (limbs) + K·VB·4
(accumulator) + VB·NB·4 iota + VB·NB·4 counts; with NB = 128, VB = 512,
K ≤ 6 that is ~0.5 MB, far under the scoped-VMEM budget, and the matmul
contraction (NB = 128) and output tile (VB = 512) are MXU-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.data.schema import PAD_ID

DEFAULT_TOKEN_BLOCK = 128   # rows per block (NB)
DEFAULT_VOCAB_BLOCK = 512   # vocab tile (VB)

# float32 mantissa budget: limb_bits + ceil(log2(contraction)) must stay <= 24
# so each limb's partial matmul is exact
_F32_EXACT_BITS = 24


def limb_split(contraction: int, acc_bits: int):
    """(limb_bits, n_limbs) for an exact split-limb accumulation.

    ``limb_bits`` is the widest limb whose partial sum over ``contraction``
    terms stays float32-exact; ``n_limbs`` covers ``acc_bits`` of weight so
    the recombined total is exact modulo ``2**acc_bits``.
    """
    limb_bits = max(1, _F32_EXACT_BITS - max(0, (contraction - 1).bit_length()))
    return limb_bits, -(-acc_bits // limb_bits)


def _token_counts(tokens_ref, vocab_block: int):
    """counts[v, r] = occurrences of term ``v0 + v`` in token row ``r``
    (PAD never counts), float32 ``[VB, NB]`` for this grid step's vocab
    tile.  One sublane-broadcast compare per token position: no reshape of
    the token block."""
    tl, nb = tokens_ref.shape
    v0 = pl.program_id(0) * vocab_block
    tok = tokens_ref[...]
    tok = jnp.where(tok == PAD_ID, -1, tok)   # -1 matches no vocab id
    vocab_ids = v0 + jax.lax.broadcasted_iota(jnp.int32, (vocab_block, nb), 0)
    counts = jnp.zeros((vocab_block, nb), jnp.float32)
    for pos in range(tl):
        counts = counts + (tok[pos:pos + 1, :] == vocab_ids).astype(
            jnp.float32)
    return counts


def _rows_dot_counts(rows, counts):
    """``rows [R, NB] · counts [VB, NB]ᵀ -> [R, VB]`` on the MXU.  HIGHEST
    is load-bearing: the default TPU matmul runs one bfloat16 pass, whose
    8-bit mantissa cannot even represent a limb value."""
    return jax.lax.dot_general(rows, counts, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.HIGHEST)


# ---------------------------------------------------------------------------
# float32-accumulator kernel (floating-point weights only)
# ---------------------------------------------------------------------------

def _fct_count_kernel(tokens_ref, weights_ref, hist_ref, *, vocab_block: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    hist_ref[...] += _rows_dot_counts(weights_ref[...],
                                      _token_counts(tokens_ref, vocab_block))


@functools.partial(jax.jit, static_argnames=("vocab", "token_block",
                                             "vocab_block", "interpret"))
def fct_count_pallas(tokens: jnp.ndarray, weights: jnp.ndarray, vocab: int,
                     token_block: int = DEFAULT_TOKEN_BLOCK,
                     vocab_block: int = DEFAULT_VOCAB_BLOCK,
                     interpret: bool = False) -> jnp.ndarray:
    """tokens [L, N] int32, lane-major (N % token_block == 0,
    vocab % vocab_block == 0); weights [N].

    float32 accumulation: exact only for totals < 2^24.  Integer weights
    should use :func:`fct_count_pallas_exact` (ops.py dispatches).
    """
    tl, n = tokens.shape
    assert n % token_block == 0 and vocab % vocab_block == 0
    grid = (vocab // vocab_block, n // token_block)
    out = pl.pallas_call(
        functools.partial(_fct_count_kernel, vocab_block=vocab_block),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tl, token_block), lambda i, j: (0, j)),
            pl.BlockSpec((1, token_block), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, vocab_block), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, vocab), jnp.float32),
        interpret=interpret,
    )(tokens, weights.astype(jnp.float32)[None, :])
    return out[0].at[PAD_ID].set(0.0)


# ---------------------------------------------------------------------------
# integer-exact kernel (split-limb int32 accumulators)
# ---------------------------------------------------------------------------

def _fct_count_exact_kernel(tokens_ref, limbs_ref, acc_ref, *,
                            vocab_block: int, limb_bits: int):
    n_limbs = limbs_ref.shape[0]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # every limb's tile contribution in one matmul; each limb row holds one
    # limb of every row's weight (all < 2^limb_bits) and a count is a row's
    # occurrences of the term, so each partial sum < 2^limb_bits * NB*L
    # <= 2^24: the float32 result is an exact integer and the int32 cast is
    # lossless
    contrib = _rows_dot_counts(limbs_ref[...].astype(jnp.float32),
                               _token_counts(tokens_ref, vocab_block))
    acc = [acc_ref[k:k + 1, :] + contrib[k:k + 1, :].astype(jnp.int32)
           for k in range(n_limbs)]
    # carry propagation on every step keeps each non-top limb < 2^limb_bits
    # (so it can never wrap int32); only the top limb may wrap, harmlessly
    # modulo the recombined output width (see module docstring)
    for k in range(n_limbs - 1):
        carry = acc[k] >> limb_bits
        acc[k] = acc[k] - (carry << limb_bits)
        acc[k + 1] = acc[k + 1] + carry
    for k in range(n_limbs):
        acc_ref[k:k + 1, :] = acc[k]


@functools.partial(jax.jit, static_argnames=("vocab", "token_block",
                                             "vocab_block", "interpret"))
def fct_count_pallas_exact(tokens: jnp.ndarray, weights: jnp.ndarray,
                           vocab: int,
                           token_block: int = DEFAULT_TOKEN_BLOCK,
                           vocab_block: int = DEFAULT_VOCAB_BLOCK,
                           interpret: bool = False) -> jnp.ndarray:
    """Integer-exact weighted histogram; tokens [L, N] int32 (lane-major),
    weights [N] int.

    Returns totals in the weights' dtype, bit-identical to the ref path's
    host-style accumulation (exact modulo 2^32 for int32 weights, modulo
    2^64 for int64) — including wrap-around, so the engine's int32 overflow
    check sees exactly what a plain int32 accumulation would have produced.
    """
    tl, n = tokens.shape
    assert n % token_block == 0 and vocab % vocab_block == 0
    assert jnp.issubdtype(weights.dtype, jnp.integer), weights.dtype
    # exactness is modulo the weight dtype's full width (int16/uint64/...
    # included): the limb count must cover it and the recombination shifts
    # must stop at it
    acc_bits = jnp.iinfo(weights.dtype).bits
    limb_bits, n_limbs = limb_split(token_block * tl, acc_bits)
    mask = (1 << limb_bits) - 1
    # split outside the kernel, lane-major [K, N]: limb k holds bits
    # [limb_bits*k, limb_bits*(k+1)) of each weight's two's-complement
    # pattern (arithmetic >> sign-extends, which keeps the mod-2^acc_bits
    # recombination exact for negatives too)
    limbs = jnp.stack([(weights >> (limb_bits * k)) & mask
                       for k in range(n_limbs)]).astype(jnp.int32)
    grid = (vocab // vocab_block, n // token_block)
    acc = pl.pallas_call(
        functools.partial(_fct_count_exact_kernel, vocab_block=vocab_block,
                          limb_bits=limb_bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tl, token_block), lambda i, j: (0, j)),
            pl.BlockSpec((n_limbs, token_block), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((n_limbs, vocab_block), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n_limbs, vocab), jnp.int32),
        interpret=interpret,
    )(tokens, limbs)
    # host-side recombination in the output dtype: limbs whose shift reaches
    # the dtype width contribute 0 modulo 2^width and are dropped (shifting
    # by >= the bit width is undefined); in-range shifts wrap as two's
    # complement, matching an integer ref accumulation bit for bit
    out = jnp.zeros((vocab,), weights.dtype)
    for k in range(n_limbs):
        shift = limb_bits * k
        if shift < acc_bits:
            out = out + (acc[k].astype(weights.dtype) << shift)
    return out.at[PAD_ID].set(0)
