"""The main path's kernels and Newton solve compile for a TPU v5e.

Each test lowers with ``interpret=False`` at the widths the secure fit
runs (d = 128 features, 2-of-3 Shamir over the CRT field, N = 25,000 rows
per institution) against a described, unattached v5e chip, so a
construct the TPU compiler refuses fails here rather than on the chip.
Nothing runs: these say nothing about results or speed.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.field import FIELD_WIDE
from repro.core.newton import newton_step
from repro.kernels.fused_irls import fused_irls_cv_pallas, fused_irls_pallas
from repro.kernels.shamir_poly import (
    shamir_encode_share_pallas,
    shamir_poly_pallas,
)
from repro.kernels.shamir_reconstruct import (
    lagrange_weights_host,
    shamir_reconstruct_pallas,
)

D = 128  # features (the acceptance width)
S = 8  # institutions
N = 25_088  # 25,000 rows per institution, padded to the 512-row block
ROWS = 1024  # (rows, 128) flat share-buffer tiles
MODULI = FIELD_WIDE.moduli
W, T = 3, 2  # 2-of-3 Shamir


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back without
    # one: keep them out of the persistent cache
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_encode_share_kernel_compiles(one_chip):
    c = _compile(
        lambda x, coeffs: shamir_encode_share_pallas(
            x, coeffs, W, MODULI, 28, block_rows=256, interpret=False),
        one_chip,
        ((ROWS, 128), jnp.float64),
        ((len(MODULI), T - 1, ROWS, 128), jnp.uint32),
    )
    _assert_kernel(c)


def test_share_kernel_compiles(one_chip):
    c = _compile(
        lambda secret, coeffs: shamir_poly_pallas(
            secret, coeffs, W, MODULI[0], block_rows=256, interpret=False),
        one_chip,
        ((ROWS, 128), jnp.uint32),
        ((T - 1, ROWS, 128), jnp.uint32),
    )
    _assert_kernel(c)


def test_reconstruct_kernel_compiles(one_chip):
    lams = lagrange_weights_host((1, 2), MODULI)
    c = _compile(
        lambda shares: shamir_reconstruct_pallas(
            shares, lams, MODULI, garner=True, block_rows=256,
            interpret=False),
        one_chip,
        ((len(MODULI), T, ROWS, 128), jnp.uint32),
    )
    _assert_kernel(c)


def test_fused_irls_kernel_compiles(one_chip):
    c = _compile(
        lambda beta, X, y, counts: fused_irls_pallas(
            beta, X, X, y, counts, block_n=512, interpret=False),
        one_chip,
        ((D,), jnp.float32),
        ((S, N, D), jnp.float32),
        ((S, N), jnp.float32),
        ((S,), jnp.int32),
    )
    _assert_kernel(c)


def test_fused_irls_cv_kernel_compiles(one_chip):
    configs = 35  # 7 lambdas x 5 folds
    c = _compile(
        lambda betas, X, y, counts, fold_ids, fold_of: fused_irls_cv_pallas(
            betas, X, X, y, counts, fold_ids, fold_of, block_n=512,
            interpret=False),
        one_chip,
        ((configs, D), jnp.float32),
        ((S, N, D), jnp.float32),
        ((S, N), jnp.float32),
        ((S,), jnp.int32),
        ((S, N), jnp.int32),
        ((configs,), jnp.int32),
    )
    _assert_kernel(c)


def test_newton_step_compiles_in_float64(one_chip):
    c = _compile(
        lambda beta, H, g: newton_step(beta, H, g, 1.0),
        one_chip,
        ((D,), jnp.float64),
        ((D, D), jnp.float64),
        ((D,), jnp.float64),
    )
    assert c.as_text()
