"""Unit tests: int8 error-feedback compression, checkpoint manager.

(The in-SPMD secure_psum coverage moved to tests/test_secure_psum.py,
parametrized over wire format, reveal mode and device counts.)"""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.checkpoint import CheckpointManager, load_pytree, save_pytree
from repro.optim.compression import compressed_psum, init_error_feedback


# ----------------------------------------------------------- compression
def test_compressed_psum_error_feedback_converges(rng_key):
    """Repeated compression of the same gradient: error feedback makes the
    running mean of dequantized values converge to the true value."""
    mesh = jax.make_mesh((1,), ("pod",))
    g = {"w": 0.1 * jax.random.normal(rng_key, (512,), jnp.float32)}
    e = init_error_feedback(g)

    def step(e):
        return jax.shard_map(
            lambda ee: compressed_psum(g, "pod", ee),
            mesh=mesh, in_specs=(P(),), out_specs=(P(), P()),
            check_vma=False,
        )(e)

    acc = jnp.zeros((512,))
    n = 20
    for _ in range(n):
        mean, e = step(e)
        acc = acc + mean["w"]
    np.testing.assert_allclose(acc / n, g["w"], atol=2e-4)


def test_compressed_psum_quantization_bounded(rng_key):
    mesh = jax.make_mesh((1,), ("pod",))
    g = {"w": jax.random.normal(rng_key, (1024,), jnp.float32)}
    e = init_error_feedback(g)
    mean, e2 = jax.shard_map(
        lambda ee: compressed_psum(g, "pod", ee),
        mesh=mesh, in_specs=(P(),), out_specs=(P(), P()),
        check_vma=False,
    )(e)
    absmax = float(jnp.max(jnp.abs(g["w"])))
    # one-shot error bounded by one quantization step
    assert float(jnp.max(jnp.abs(mean["w"] - g["w"]))) <= absmax / 127 + 1e-6
    # residual carries exactly the quantization error
    np.testing.assert_allclose(np.asarray(e2["w"]),
                               np.asarray(g["w"] - mean["w"]), atol=1e-6)


# ------------------------------------------------------------ checkpoint
def _tree(key):
    return {
        "a": jax.random.normal(key, (8, 4), jnp.float32),
        "b": {"c": jnp.arange(6, dtype=jnp.int32),
              "d": jax.random.normal(jax.random.fold_in(key, 1), (3,),
                                     jnp.bfloat16)},
    }


def test_save_load_roundtrip_bf16(tmp_path, rng_key):
    tree = _tree(rng_key)
    path = str(tmp_path / "t.npz")
    save_pytree(tree, path)
    out = load_pytree(tree, path)
    for a, b in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(tree)):
        assert a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32))


def test_checkpoint_manager_retention_and_restore(tmp_path, rng_key):
    mgr = CheckpointManager(str(tmp_path), retain=2)
    trees = {}
    for step in (1, 2, 3, 4):
        t = _tree(jax.random.fold_in(rng_key, step))
        trees[step] = t
        mgr.save(step, t)
    assert mgr.steps() == [3, 4]  # retain-2 GC
    restored, step = mgr.restore(trees[4])
    assert step == 4
    np.testing.assert_allclose(np.asarray(restored["a"]),
                               np.asarray(trees[4]["a"]))


def test_checkpoint_manager_async_writes(tmp_path, rng_key):
    mgr = CheckpointManager(str(tmp_path), retain=3, async_writes=True)
    t = _tree(rng_key)
    mgr.save(7, t)
    mgr.close()  # drains the writer thread
    restored, step = mgr.restore(t)
    assert step == 7
    np.testing.assert_allclose(np.asarray(restored["a"]),
                               np.asarray(t["a"]))


