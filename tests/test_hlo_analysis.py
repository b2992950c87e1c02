"""Unit tests for the roofline estimator (launch/hlo_analysis.py).

The §Roofline tables and §Perf iteration verdicts all read through this
module, so its conventions are pinned here against hand-computable
micro-kernels: dot flops, loop trip-count multiplication, slice-aware
fusion operands, in-place dynamic-update-slice accounting, and collective
byte conventions.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_analysis import analyze_hlo


def _cost(fn, *specs):
    return analyze_hlo(jax.jit(fn).lower(*specs).compile().as_text())


def test_matmul_flops_exact():
    h = _cost(lambda a, b: a @ b,
              jax.ShapeDtypeStruct((256, 512), jnp.float32),
              jax.ShapeDtypeStruct((512, 128), jnp.float32))
    assert h.flops == 2 * 256 * 512 * 128
    # bytes: a + b + out
    expect = 4 * (256 * 512 + 512 * 128 + 256 * 128)
    assert h.bytes == pytest.approx(expect, rel=0.05)


def test_scan_trip_count_multiplies_flops():
    def f(a):
        def step(c, _):
            return c @ c * 0.5, None
        return jax.lax.scan(step, a, None, length=7)[0]

    h = _cost(f, jax.ShapeDtypeStruct((128, 128), jnp.float32))
    assert h.flops == 7 * 2 * 128**3


def test_scan_output_dus_not_charged_full_buffer():
    """Scan stacking a large output writes via in-place DUS; per trip we
    must charge ~the slice, not the whole stacked buffer."""
    def f(a):
        def step(c, _):
            c = c * 1.0001
            return c, c
        _, ys = jax.lax.scan(step, a, None, length=64)
        return ys  # (64, 256, 256)

    h = _cost(f, jax.ShapeDtypeStruct((256, 256), jnp.float32))
    full_buffer_per_trip = 64 * 64 * 256 * 256 * 4  # the wrong accounting
    assert h.bytes < 0.25 * full_buffer_per_trip
    # and at least the genuine traffic: 64 x (read c + write c + write ys)
    assert h.bytes > 64 * 2 * 256 * 256 * 4


def test_sliced_scan_param_not_charged_full_stack():
    """A scan slicing per-layer weights from a stacked (L, d, d) operand
    must charge the slice, not L x the stack per trip."""
    def f(x, w_stack):
        def step(c, w):
            return jnp.tanh(c @ w), None
        return jax.lax.scan(step, x, w_stack)[0]

    L, d = 16, 128
    h = _cost(f, jax.ShapeDtypeStruct((d, d), jnp.float32),
              jax.ShapeDtypeStruct((L, d, d), jnp.float32))
    # flops: L x dxd matmuls
    assert h.flops == L * 2 * d**3
    # bytes should be ~L x (one slice + carry io), nowhere near L x stack
    assert h.bytes < 3 * L * d * d * 4 * 4


def test_collective_conventions():
    mesh = jax.make_mesh((1,), ("x",))

    def f(a):
        return jax.lax.psum(a, "x")

    from jax.sharding import PartitionSpec as P

    fn = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P()))
    txt = fn.lower(jax.ShapeDtypeStruct((1024,), jnp.float32)) \
            .compile().as_text()
    h = analyze_hlo(txt)
    if h.collective_count:  # single-device AR may be optimized away
        assert h.collective_bytes["all-reduce"] == 2 * 1024 * 4  # 2x rule


_RS_AG_HLO = """
HloModule rs_ag

ENTRY %main (p0: f32[1024]) -> f32[1024] {
  %p0 = f32[1024]{0} parameter(0)
  %rs = f32[256]{0} reduce-scatter(%p0), replica_groups={{0,1,2,3}}, dimensions={0}, to_apply=%add
  ROOT %ag = f32[1024]{0} all-gather(%rs), replica_groups={{0,1,2,3}}, dimensions={0}
}
"""

_AR_HLO = """
HloModule ar

ENTRY %main (p0: f32[1024]) -> f32[1024] {
  %p0 = f32[1024]{0} parameter(0)
  ROOT %ar = f32[1024]{0} all-reduce(%p0), replica_groups={{0,1,2,3}}, to_apply=%add
}
"""


def test_reduce_scatter_counts_operand_bytes():
    """RS moves the full operand (4096 B), not its 1/D-sized result."""
    h = analyze_hlo(_RS_AG_HLO)
    assert h.collective_count["reduce-scatter"] == 1
    assert h.collective_bytes["reduce-scatter"] == 1024 * 4


def test_all_gather_counts_result_bytes():
    """AG's traffic is the full gathered buffer it produces."""
    h = analyze_hlo(_RS_AG_HLO)
    assert h.collective_count["all-gather"] == 1
    assert h.collective_bytes["all-gather"] == 1024 * 4


def test_rs_ag_pair_matches_all_reduce():
    """The conventions must be self-consistent: decomposing an AR into
    its RS + AG phases may not change the collective-bytes total."""
    pair = analyze_hlo(_RS_AG_HLO)
    ar = analyze_hlo(_AR_HLO)
    assert ar.collective_bytes["all-reduce"] == 2 * 1024 * 4
    assert (pair.collective_bytes["reduce-scatter"]
            + pair.collective_bytes["all-gather"]
            ) == ar.collective_bytes["all-reduce"]


def test_nested_scan_trip_products():
    def f(a):
        def outer(c, _):
            def inner(ci, _):
                return ci @ ci, None
            c, _ = jax.lax.scan(inner, c, None, length=3)
            return c, None
        return jax.lax.scan(outer, a, None, length=5)[0]

    h = _cost(f, jax.ShapeDtypeStruct((64, 64), jnp.float32))
    assert h.flops == 5 * 3 * 2 * 64**3
