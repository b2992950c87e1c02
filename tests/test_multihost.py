"""Multi-host SPMD rounds: 2D (pod, share) mesh, sharded-tile aggregates,
and the XLA flag/knob plumbing that gets a CPU mesh up in CI.

The mesh tests run in a subprocess because XLA_FLAGS must be owned before
jax initializes (same constraint ``distributed.xla_flags`` encodes); the
flag-builder and kernel-knob tests are plain host-side unit tests.
"""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.distributed.xla_flags import (
    LATENCY_HIDING_FLAGS,
    _merge_flags,
    apply_xla_flags,
    jax_backend_initialized,
    mesh_env,
)
from repro.kernels.tuning import (
    DEFAULT_KNOBS,
    KernelKnobs,
    validate_real_kernel_knobs,
    vmem_bytes,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ xla_flags

def test_merge_flags_last_writer_wins_per_flag():
    merged = _merge_flags(
        "--xla_force_host_platform_device_count=3 --a=1",
        ["--xla_force_host_platform_device_count=8", "--b=2"],
    )
    assert merged == ("--xla_force_host_platform_device_count=8 "
                      "--a=1 --b=2")


def test_mesh_env_builds_child_flags_without_touching_parent():
    before = os.environ.get("XLA_FLAGS")
    env = mesh_env(host_device_count=6, base={"PATH": "/bin"})
    assert env["XLA_FLAGS"] == "--xla_force_host_platform_device_count=6"
    assert env["PATH"] == "/bin"
    # a forced-host-device child is a CPU mesh: it must never claim the
    # accelerator its parent holds
    assert env["JAX_PLATFORMS"] == "cpu"
    assert os.environ.get("XLA_FLAGS") == before


def test_mesh_env_latency_hiding_is_gpu_only_opt_in():
    """The GPU collective-overlap flags appear only on request: XLA
    hard-aborts on unknown flags, and CPU builds do not register the
    --xla_gpu_* family, so CPU-mesh children must never inherit them."""
    plain = mesh_env(host_device_count=4, base={})
    assert "--xla_gpu_" not in plain["XLA_FLAGS"]
    gpu = mesh_env(host_device_count=4, latency_hiding=True, base={})
    for flag in LATENCY_HIDING_FLAGS:
        assert flag in gpu["XLA_FLAGS"]


def test_apply_xla_flags_refuses_post_init_changes():
    """This test session has a live jax backend, so any CHANGE must
    raise; re-applying the current value stays idempotent."""
    import jax

    jax.devices()
    assert jax_backend_initialized()
    current = os.environ.get("XLA_FLAGS", "")
    assert apply_xla_flags() == current
    with pytest.raises(RuntimeError, match="already initialized"):
        apply_xla_flags(extra=("--xla_definitely_not_set_yet=1",))
    assert os.environ.get("XLA_FLAGS", "") == current


def test_initialize_distributed_noop_outside_multiprocess(monkeypatch):
    from repro.distributed.multihost import initialize_distributed

    monkeypatch.delenv("JAX_NUM_PROCESSES", raising=False)
    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    assert initialize_distributed() is False
    monkeypatch.setenv("JAX_NUM_PROCESSES", "1")
    assert initialize_distributed() is False


# ------------------------------------------------------- kernel knobs

def test_default_knobs_validate_under_vmem_limit():
    reports = validate_real_kernel_knobs()
    assert {r["kernel"] for r in reports} == set(DEFAULT_KNOBS)
    assert all(r["ok"] for r in reports)
    assert all(r["vmem_bytes"] <= r["vmem_limit_bytes"] for r in reports)


def test_knob_validation_rejects_misaligned_and_oversized():
    bad = dict(DEFAULT_KNOBS)
    bad["fused_irls"] = bad["fused_irls"].replace(block_n=500)
    with pytest.raises(ValueError, match="sublane"):
        validate_real_kernel_knobs(bad)
    huge = dict(DEFAULT_KNOBS)
    huge["fused_irls"] = huge["fused_irls"].replace(block_n=1 << 20)
    with pytest.raises(ValueError, match="VMEM"):
        validate_real_kernel_knobs(huge)
    with pytest.raises(ValueError, match="128"):
        validate_real_kernel_knobs(d=100)


def test_vmem_model_monotone_in_block_size():
    small = vmem_bytes(KernelKnobs("fused_irls", block_n=256))
    big = vmem_bytes(KernelKnobs("fused_irls", block_n=1024))
    assert small < big


# ------------------------------------------------- CPU-mesh subprocess

_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=6"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core.secure_agg import SecureAggregator, secure_psum
    from repro.core.flatbuf import pack_pytree, unpack_pytree_tile
    from repro.distributed.multihost import (
        pod_mesh, pod_share_mesh, secure_psum_2d, run_scanned_rounds)
    from repro.distributed.sharding import POD_AXIS

    tree = {
        "g": 0.5 * jax.random.normal(jax.random.PRNGKey(1), (300,),
                                     jnp.float32),
        "h": jnp.float32(3.25) * jnp.ones((4, 4), jnp.float32),
    }
    agg = SecureAggregator(backend="pallas")

    # out="tile" keeps the decoded aggregate sharded; gather must equal
    # the replicated out="tree" decode bitwise on an uneven (D=3) mesh.
    D = 3
    mesh = pod_mesh(D)
    tree_out = jax.shard_map(
        lambda: secure_psum(tree, POD_AXIS, jax.random.PRNGKey(5),
                            aggregator=agg, reveal="sharded"),
        mesh=mesh, in_specs=(), out_specs=P(), check_vma=False)()
    tile_out = jax.shard_map(
        lambda: secure_psum(tree, POD_AXIS, jax.random.PRNGKey(5),
                            aggregator=agg, reveal="sharded", out="tile"
                            ).gather(POD_AXIS),
        mesh=mesh, in_specs=(), out_specs=P(), check_vma=False)()
    for k in tree:
        np.testing.assert_array_equal(np.asarray(tree_out[k]),
                                      np.asarray(tile_out[k]))
        np.testing.assert_allclose(np.asarray(tile_out[k]),
                                   D * np.asarray(tree[k]), atol=1e-5)

    # host-side tile addressing: per-tile fragments re-assemble the tree
    buf, layout = pack_pytree(tree, row_align=24)  # lcm(8, 3)
    flat_ref = np.concatenate([np.ravel(np.asarray(tree["g"])),
                               np.ravel(np.asarray(tree["h"]))])
    tiles = np.asarray(buf).reshape(3, -1)
    for t in range(3):
        frags = unpack_pytree_tile(
            jnp.asarray(tiles[t].reshape(-1, 128)), layout, t, 3)
        for leaf, (a, b, frag) in frags.items():
            base = 0 if leaf == 0 else tree["g"].size
            np.testing.assert_allclose(np.asarray(frag),
                                       flat_ref[base + a: base + b],
                                       atol=1e-6)

    # 2D (pod, share) mesh: the distributed Lagrange reveal (share slice
    # x public weight, psum over the share axis) must equal the 1D wire
    # bitwise -- same sharing polynomials, same field reconstruction.
    mesh2 = pod_share_mesh(3, agg.scheme.threshold)
    out2 = jax.shard_map(
        lambda: secure_psum_2d(tree, jax.random.PRNGKey(5),
                               aggregator=agg),
        mesh=mesh2, in_specs=(), out_specs=P(), check_vma=False)()
    for k in tree:
        np.testing.assert_array_equal(np.asarray(tree_out[k]),
                                      np.asarray(out2[k]))

    # scanned rounds: protect -> aggregate -> reveal chained in-graph is
    # mean-preserving round over round (reveal of round r feeds r+1)
    for reveal in ("replicated", "sharded"):
        final, trace = run_scanned_rounds(
            3, tree, jax.random.PRNGKey(7), 4, aggregator=agg,
            reveal=reveal)
        for k in tree:
            np.testing.assert_allclose(np.asarray(final[k]),
                                       np.asarray(tree[k]), atol=1e-4)
        assert trace.shape == (4,)
    print("MULTIHOST_MESH_OK")
""")


def test_multihost_cpu_mesh(tmp_path):
    """6 forced host devices: sharded-tile parity, 2D distributed reveal
    bitwise vs the 1D wire, and the in-graph scanned round chain."""
    script = tmp_path / "multihost_mesh.py"
    script.write_text(_MESH_SCRIPT)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "MULTIHOST_MESH_OK" in r.stdout
