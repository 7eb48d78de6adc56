"""Fault tolerance: checkpoint/restart, failure injection, elastic restore,
gradient compression, skew scheduler."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.core.skew import lpt_schedule, round_robin_schedule
from repro.distributed.checkpoint import (latest_step, restore_checkpoint,
                                          save_checkpoint)
from repro.distributed.compression import (compressed_psum, dequantize_leaf,
                                           init_error_state, quantize_leaf)
from repro.train.loop import LoopConfig, train


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": {"c": jnp.ones((2,), jnp.bfloat16),
                  "d": jnp.asarray(3, jnp.int32)}}
    save_checkpoint(str(tmp_path), 7, tree)
    assert latest_step(str(tmp_path)) == 7
    step, got = restore_checkpoint(str(tmp_path), tree)
    assert step == 7
    for l1, l2 in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(l1, np.float32),
                                      np.asarray(l2, np.float32))


def test_checkpoint_prunes_old_steps(tmp_path):
    tree = {"x": jnp.zeros((2,))}
    for s in (1, 2, 3, 4, 5):
        save_checkpoint(str(tmp_path), s, tree, keep=2)
    steps = sorted(p.name for p in tmp_path.iterdir()
                   if p.name.startswith("step_"))
    assert steps == ["step_00000004", "step_00000005"]


def test_failure_injection_and_resume_is_deterministic(tmp_path):
    """Crash at step 7, restart, and land on the SAME final loss as an
    uninterrupted run — checkpoint/restart is bit-compatible in expectation."""
    cfg = get_arch("olmo_1b").reduced()
    ref = train(cfg, LoopConfig(steps=10, ckpt_dir=None, log_every=0))
    ck = str(tmp_path / "ck")
    with pytest.raises(RuntimeError, match="injected failure"):
        train(cfg, LoopConfig(steps=10, ckpt_dir=ck, ckpt_every=2,
                              log_every=0, fail_at_step=7))
    assert latest_step(ck) == 6
    resumed = train(cfg, LoopConfig(steps=10, ckpt_dir=ck, ckpt_every=2,
                                    log_every=0))
    np.testing.assert_allclose(resumed["final_loss"], ref["final_loss"],
                               rtol=2e-4)


def test_elastic_restore_onto_new_sharding(tmp_path):
    """Save unsharded, restore onto explicit device placement (the re-mesh
    path; with 1 CPU device the sharding is trivial but the code path is
    identical to the 256->512 chip restart)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    tree = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4)}
    save_checkpoint(str(tmp_path), 1, tree)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    sh = {"w": NamedSharding(mesh, P("data", None))}
    _, got = restore_checkpoint(str(tmp_path), tree, shardings=sh)
    assert got["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(tree["w"]))


def test_quantize_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(64, 32)) * 5, jnp.float32)
    q, scale = quantize_leaf(g)
    err = np.abs(np.asarray(dequantize_leaf(q, scale) - g))
    assert err.max() <= float(scale) / 2 + 1e-6


def test_compressed_psum_error_feedback_converges():
    """Over repeated steps with constant gradient, error feedback makes the
    AVERAGE applied gradient converge to the true one."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    g_true = {"w": jnp.asarray(
        np.random.default_rng(1).normal(size=(32, 16)), jnp.float32)}

    def step(err_leaf):
        err = {"w": err_leaf}
        fn = shard_map(lambda e: compressed_psum(g_true, {"w": e}, "dp"),
                       mesh=mesh, in_specs=P(), out_specs=P(),
                       check_vma=False)
        mean, new_err = fn(err["w"])
        return mean, new_err

    err = init_error_state(g_true)["w"]
    applied = jnp.zeros_like(g_true["w"])
    n = 20
    for _ in range(n):
        mean, err_d = step(err)
        err = err_d["w"]
        applied = applied + mean["w"]
    avg = applied / n
    rel = float(jnp.linalg.norm(avg - g_true["w"])
                / jnp.linalg.norm(g_true["w"]))
    assert rel < 0.02, rel


def test_lpt_beats_round_robin_on_skewed_costs():
    rng = np.random.default_rng(0)
    costs = rng.zipf(1.3, size=64).astype(np.float64)
    lpt = lpt_schedule(costs, 8)
    rr = round_robin_schedule(costs, 8)
    assert lpt.imbalance <= rr.imbalance + 1e-9


def test_lpt_prunes_empty_tasks():
    costs = np.array([5.0, 3.0, 2.0, 1.0])
    empty = np.array([False, True, False, False])
    sch = lpt_schedule(costs, 2, prune_empty=empty)
    assert sch.task_to_device[1] == -1
    assert (sch.task_to_device[[0, 2, 3]] >= 0).all()
