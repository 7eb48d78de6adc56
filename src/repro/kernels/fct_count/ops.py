"""Public op: weighted token histogram with backend dispatch.

Backend dispatch table (see ``src/repro/kernels/README.md``):

    backend="auto"       TPU -> "pallas", anything else -> "ref"
    backend="pallas"     integer weights -> split-limb integer-exact kernel
                         (kernel.fct_count_pallas_exact, bit-identical to
                         the ref path modulo the weight dtype's width);
                         floating weights -> float32-accumulator kernel
                         (exact only for totals < 2^24)
    backend="ref"        pure-jnp segment-sum oracle (ref.py), any dtype
    backend="interpret"  legacy spelling of backend="pallas", interpret=True

Tokens are LANE-MAJOR, ``[L, N]``: one row per token position, the
relation's rows along the minor axis (the row-major ``[N, L]`` layout pads
its narrow minor axis to 128 lanes in TPU memory; see kernel.py).

``interpret=True`` executes the selected Pallas kernel body through the
Pallas interpreter (CPU) — how tests and the CI x64 job drive the kernel
without a TPU.  int64 weights (the engine's INT64_EXACT accumulation
policy) ride the exact kernel like int32 ones; the old behavior of forcing
them onto the ref path is retired.

``PATH_COUNTS`` tallies which path each *traced* call took ("ref",
"pallas_exact", "pallas_float") — the counters move at trace time, so a
fresh-cache query reveals exactly which code path its compiled programs
embed; tests assert x64 serving hits zero ref fallbacks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.fct_count import ref
from repro.kernels.fct_count.kernel import (DEFAULT_TOKEN_BLOCK,
                                            DEFAULT_VOCAB_BLOCK,
                                            fct_count_pallas,
                                            fct_count_pallas_exact)

PATH_COUNTS = {"ref": 0, "pallas_exact": 0, "pallas_float": 0}


def reset_path_counts() -> None:
    for k in PATH_COUNTS:
        PATH_COUNTS[k] = 0


def _pad_to(x: jnp.ndarray, multiple: int, value) -> jnp.ndarray:
    """Pad the LAST axis (the rows) up to a multiple."""
    pad = (-x.shape[-1]) % multiple
    if pad == 0:
        return x
    cfg = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, cfg, constant_values=value)


def weighted_histogram(tokens: jnp.ndarray, weights: jnp.ndarray, vocab: int,
                       backend: str = "auto",
                       interpret: bool = False) -> jnp.ndarray:
    """freq[w] = Σ_rows weight[row]·count(tokens[:, row], w); PAD excluded.

    tokens [L, N] int32 (lane-major), weights [N].  Output dtype follows
    ``weights``.  Integer weights (int32, and int64 under
    ``jax_enable_x64``) take the split-limb integer-exact kernel on
    the pallas path: totals are bit-identical to the ref path's integer
    accumulation — wrap-around included, so the runtime's AccumPolicy
    overflow check behaves the same on every backend.  Floating weights
    keep the float32-accumulator kernel (exact only for totals < 2^24).
    """
    if backend == "auto":
        platform = jax.default_backend()
        backend = "pallas" if platform == "tpu" else "ref"
    if backend == "interpret":   # legacy spelling
        backend, interpret = "pallas", True
    if backend == "ref":
        PATH_COUNTS["ref"] += 1
        return ref.weighted_histogram(tokens, weights, vocab)
    if backend != "pallas":
        raise ValueError(f"unknown fct_count backend {backend!r}")
    vb, padded_vocab = _pick_block(vocab)
    toks = _pad_to(tokens, DEFAULT_TOKEN_BLOCK, 0)
    w = _pad_to(weights, DEFAULT_TOKEN_BLOCK, 0)
    if jnp.issubdtype(weights.dtype, jnp.integer):
        PATH_COUNTS["pallas_exact"] += 1
        out = fct_count_pallas_exact(toks, w, padded_vocab, vocab_block=vb,
                                     interpret=interpret)
    else:
        PATH_COUNTS["pallas_float"] += 1
        out = fct_count_pallas(toks, w, padded_vocab, vocab_block=vb,
                               interpret=interpret)
    if padded_vocab != vocab:
        out = out[:vocab]
    return out.astype(weights.dtype)


def _pick_block(vocab: int):
    """(vocab_block, padded_vocab): ragged vocabs pad up to a lane-aligned
    multiple of 128 (tokens are < vocab, so the tail slots stay zero and are
    sliced off) instead of degrading to a vocab-sized grid of 1-wide tiles."""
    if vocab % DEFAULT_VOCAB_BLOCK == 0:
        return DEFAULT_VOCAB_BLOCK, vocab
    padded = -(-vocab // 128) * 128
    for vb in (DEFAULT_VOCAB_BLOCK, 256, 128):
        if padded % vb == 0:
            return vb, padded
    raise AssertionError(padded)  # unreachable: padded is a 128-multiple
