"""Kernel micro-benchmarks: the MR² weighted-histogram hot spot and the
flash-attention/LRU oracles.

On this CPU container the Pallas kernels run in interpret mode (correctness,
not speed), so wall times compare the XLA ref paths; the derived column
carries the TPU-side analytic estimate for the kernel (MXU/VPU-bound time at
v5e rates) so the §Perf napkin math is reproducible.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timed
from repro.kernels.fct_count import ref as fct_ref
from repro.kernels.fct_count.ops import weighted_histogram
from repro.kernels.flash_attention import ref as flash_ref
from repro.kernels.lru_scan import ref as lru_ref

PEAK = 197e12
HBM = 819e9


def run():
    rng = np.random.default_rng(0)

    # fct_count: N x L tokens histogrammed over V
    n, tl, v = 8192, 16, 32768
    toks = jnp.asarray(rng.integers(0, v, (tl, n)), jnp.int32)  # [L, N]
    w = jnp.asarray(rng.integers(0, 9, (n,)), jnp.int32)
    ref_fn = jax.jit(lambda t, ww: fct_ref.weighted_histogram(t, ww, v))
    us = timed(lambda: jax.block_until_ready(ref_fn(toks, w)))
    mxu_s = (2.0 * n * tl * v) / PEAK           # one-hot matmul flops
    hbm_s = (n * tl * 4 + v * 4) / HBM
    emit("fct_count/ref_segment_sum", us,
         f"tpu_kernel_est_us={max(mxu_s, hbm_s) * 1e6:.1f}")

    small = jnp.asarray(rng.integers(0, 512, (8, 256)), jnp.int32)  # [L, N]
    sw = jnp.asarray(rng.integers(0, 9, (256,)), jnp.int32)
    us = timed(lambda: jax.block_until_ready(
        weighted_histogram(small, sw, 512, backend="interpret")), iters=1)
    emit("fct_count/pallas_interpret_small", us, "correctness-mode timing")

    # flash attention ref (the model hot path on the XLA side)
    b, s, h, d = 1, 2048, 8, 64
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.bfloat16)
    vv = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.bfloat16)
    fa = jax.jit(lambda q, k, v: flash_ref.flash_attention(q, k, v,
                                                           causal=True))
    us = timed(lambda: jax.block_until_ready(fa(q, k, vv)))
    flops = 4.0 * b * h * s * s * d
    emit("flash_attention/ref_2k", us,
         f"tpu_kernel_est_us={flops / PEAK * 1e6:.1f}")

    # lru scan ref
    a = jnp.asarray(rng.uniform(0.9, 1.0, (4, 4096, 512)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(4, 4096, 512)), jnp.float32)
    ls = jax.jit(lru_ref.lru_scan)
    us = timed(lambda: jax.block_until_ready(ls(a, x)))
    one_pass = 3 * a.size * 4 / HBM            # read a,b + write h once
    emit("lru_scan/ref_assoc_scan", us,
         f"tpu_kernel_est_us={one_pass * 1e6:.1f} (1-pass HBM bound)")
