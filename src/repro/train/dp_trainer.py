"""Explicit data-parallel trainer with int8 error-feedback gradient
compression (DESIGN.md §5 "distributed-optimization tricks").

The pjit trainer (train/step.py) lets GSPMD reduce gradients exactly; this
variant computes per-replica gradients under ``shard_map`` and reduces them
with ``compressed_psum`` — 8× less DP wire traffic than fp32, with the
quantization residual carried forward per replica (error feedback).  On the
2×16×16 mesh this is the cross-pod reduction, i.e. the slowest link.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.distributed.compression import compressed_psum, init_error_state
from repro.models import model as model_lib
from repro.train.optimizer import AdamWConfig, adamw_update


def make_compressed_dp_step(cfg: ArchConfig, mesh: Mesh, axis: str = "data",
                            opt_cfg: AdamWConfig = AdamWConfig(),
                            compress: bool = True):
    """Returns step(params, opt_state, err_state, batch) -> (..., metrics).

    params/opt replicated; batch sharded on ``axis``; gradients reduced with
    the compressed collective (or exact psum when compress=False).
    """
    def local_step(params, opt_state, err, batch):
        (total, metrics), grads = jax.value_and_grad(
            model_lib.loss_fn, has_aux=True)(params, batch, cfg)
        if compress:
            grads, err = compressed_psum(grads, err, axis)
        else:
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, axis), grads)
        params, opt_state, gnorm = adamw_update(grads, opt_state, params,
                                                opt_cfg)
        metrics = dict(metrics, total=jax.lax.pmean(total, axis),
                       grad_norm=gnorm)
        return params, opt_state, err, metrics

    rep = P()
    batch_spec = jax.tree.map(lambda _: P(axis),
                              model_lib.make_dummy_batch(
                                  cfg, mesh.shape[axis], 4,
                                  jax.random.PRNGKey(0)))
    fn = shard_map(local_step, mesh=mesh,
                   in_specs=(rep, rep, rep, batch_spec),
                   out_specs=(rep, rep, rep, rep),
                   check_vma=False)
    return jax.jit(fn)


def init_error(params):
    return init_error_state(params)
