"""Pure-jnp oracle for the weighted token histogram (MR² inner loop).

freq[w] = Σ_rows weight[row] · count(tokens[:, row], w),   PAD excluded.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.data.schema import PAD_ID


def weighted_histogram(tokens: jnp.ndarray, weights: jnp.ndarray,
                       vocab: int) -> jnp.ndarray:
    """tokens [L, N] int32 (lane-major), weights [N] (int32/float32) ->
    [vocab]."""
    flat = tokens.reshape(-1)
    w = jnp.broadcast_to(weights[None, :], tokens.shape).reshape(-1)
    w = jnp.where(flat == PAD_ID, 0, w)
    hist = jnp.zeros((vocab,), weights.dtype).at[flat].add(w, mode="drop")
    return hist.at[PAD_ID].set(0)
