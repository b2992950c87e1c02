"""In-SPMD secure_psum: flat-buffer wire vs per-leaf oracle, reveal modes,
t-subset reconstruction, overflow/headroom guards.

The single-device matrix runs in-process; the uneven-device-count case
(mesh sizes that do not divide the 8-row sublane alignment) runs as a
subprocess because XLA_FLAGS must be owned before jax initializes (same
idiom as test_dryrun_smoke).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.secure_agg import (
    SecureAggregator,
    check_aggregation_headroom,
    secure_psum,
)
from repro.core.shamir import ShamirScheme

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tree(key):
    return {
        "g": 0.5 * jax.random.normal(key, (300,), jnp.float32),
        "h": jnp.float32(3.25) * jnp.ones((4, 4), jnp.float32),
    }


def _run_psum(tree, agg, reveal, points=None):
    mesh = jax.make_mesh((1,), ("pod",))
    return jax.shard_map(
        lambda: secure_psum(tree, "pod", jax.random.PRNGKey(5),
                            aggregator=agg, reveal=reveal, points=points),
        mesh=mesh, in_specs=(), out_specs=P(), check_vma=False,
    )()


@pytest.mark.parametrize("backend,reveal", [
    ("reference", "replicated"),
    ("pallas", "replicated"),
    ("pallas", "sharded"),
])
def test_secure_psum_exact_inside_spmd(backend, reveal, rng_key):
    """Every backend x reveal mode reveals exactly the global sum."""
    tree = _tree(rng_key)
    out = _run_psum(tree, SecureAggregator(backend=backend), reveal)
    for k in tree:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(tree[k]),
                                   atol=1e-5)


def test_secure_psum_backends_agree_bitwise(rng_key):
    """Flat wire == per-leaf oracle, bit-for-bit: both reveal the exact
    field encoding of the sum, so the decoded floats are identical."""
    tree = _tree(rng_key)
    ref = _run_psum(tree, SecureAggregator(backend="reference"), "replicated")
    for reveal in ("replicated", "sharded"):
        pal = _run_psum(tree, SecureAggregator(backend="pallas"), reveal)
        for k in tree:
            np.testing.assert_array_equal(np.asarray(ref[k]),
                                          np.asarray(pal[k]))


def test_secure_psum_any_t_subset_matches(rng_key):
    """Reveal from ANY t-subset of points == the default reconstruction
    (exact field arithmetic), on both backends."""
    tree = _tree(rng_key)
    subsets = [(1, 2), (2, 5), (3, 4), (1, 5)]
    for backend in ("reference", "pallas"):
        agg = SecureAggregator(
            scheme=ShamirScheme(threshold=2, num_shares=5, backend=backend)
        )
        base = _run_psum(tree, agg, "replicated")
        for pts in subsets:
            got = _run_psum(tree, agg, "replicated", points=pts)
            for k in tree:
                np.testing.assert_array_equal(np.asarray(base[k]),
                                              np.asarray(got[k]))


def test_secure_psum_below_threshold_raises(rng_key):
    """A short point list must raise, never reduce a short share axis."""
    tree = _tree(rng_key)
    for backend in ("reference", "pallas"):
        agg = SecureAggregator(
            scheme=ShamirScheme(threshold=3, num_shares=5, backend=backend)
        )
        with pytest.raises(ValueError, match="irrecoverable"):
            _run_psum(tree, agg, "replicated", points=(1, 2))


def test_secure_psum_sharded_requires_flat_wire(rng_key):
    with pytest.raises(ValueError, match="sharded"):
        _run_psum(_tree(rng_key), SecureAggregator(backend="reference"),
                  "sharded")
    with pytest.raises(ValueError, match="reveal"):
        _run_psum(_tree(rng_key), SecureAggregator(backend="pallas"),
                  "scattered")


def test_aggregation_headroom_guard():
    """The shared exact-sum bound: S * max(p_r) < 2**64."""
    field = SecureAggregator().scheme.field
    check_aggregation_headroom(2**33, field)  # 2**33 * (2**31 - 1) fits
    with pytest.raises(ValueError, match="2\\*\\*64"):
        check_aggregation_headroom(2**34, field)


_MULTIDEV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=3"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core.secure_agg import SecureAggregator, secure_psum

    D = 3  # does not divide the 8-row sublane alignment: rows pad to lcm
    tree = {
        "g": 0.5 * jax.random.normal(jax.random.PRNGKey(1), (300,),
                                     jnp.float32),
        "h": jnp.float32(3.25) * jnp.ones((4, 4), jnp.float32),
    }
    mesh = jax.make_mesh((D,), ("pod",))
    outs = {}
    for backend, reveal in (("reference", "replicated"),
                            ("pallas", "replicated"),
                            ("pallas", "sharded")):
        agg = SecureAggregator(backend=backend)
        out = jax.shard_map(
            lambda: secure_psum(tree, "pod", jax.random.PRNGKey(5),
                                aggregator=agg, reveal=reveal),
            mesh=mesh, in_specs=(), out_specs=P(), check_vma=False,
        )()
        outs[(backend, reveal)] = out
        for k in tree:
            np.testing.assert_allclose(
                np.asarray(out[k]), D * np.asarray(tree[k]), atol=1e-5)
    ref = outs[("reference", "replicated")]
    for combo, out in outs.items():
        for k in tree:
            np.testing.assert_array_equal(np.asarray(ref[k]),
                                          np.asarray(out[k]))
    print("MULTIDEV_OK")
""")


def test_secure_psum_uneven_device_count(tmp_path):
    """3 devices (rows pad to lcm(8, 3)): all wire formats and reveal
    modes agree bitwise and match D * tree.  Subprocess: the forced host
    device count must be set before jax initializes."""
    script = tmp_path / "psum_multidev.py"
    script.write_text(_MULTIDEV_SCRIPT)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "MULTIDEV_OK" in r.stdout
