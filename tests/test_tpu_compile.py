"""The main path's kernels and Newton solve compile for a TPU v5e.

Each test lowers with ``interpret=False`` at the widths the secure fit
runs (d = 128 features, 2-of-3 Shamir over the CRT field, N = 25,000 rows
per institution) against a described, unattached v5e chip, so a
construct the TPU compiler refuses fails here rather than on the chip.
Nothing runs: these say nothing about results or speed.  The names a
device trace reads are checked here too: each launch's kernel name, and
the named scopes of the fit program as the chip runs it.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels.backend as kernel_backend
import repro.kernels.ops as kernel_ops
from repro.core import SecureCollective
from repro.core.batched_summaries import _reference_summaries, pack_partitions
from repro.core.field import FIELD_WIDE
from repro.core.newton import newton_step
from repro.core.scanfit import fit_scan_block, pod_partitioner
from repro.data import generate_synthetic
from repro.kernels.fused_irls import (
    fused_irls_cv_pallas,
    fused_irls_pallas,
    gram_hessian_pallas,
)
from repro.kernels.shamir_poly import (
    shamir_encode_share_pallas,
    shamir_poly_pallas,
)
from repro.kernels.shamir_reconstruct import (
    lagrange_weights_host,
    shamir_reconstruct_pallas,
)
from repro.kernels.sliced_terms import K, SLAB, XSlices, sliced_terms

from bench.scopes import SCOPES, hlo_index

D = 128  # features (the acceptance width)
S = 8  # institutions
N = 25_088  # 25,000 rows per institution, padded to the 512-row block
ROWS = 1024  # (rows, 128) flat share-buffer tiles
MODULI = FIELD_WIDE.moduli
W, T = 3, 2  # 2-of-3 Shamir


@pytest.fixture(scope="module")
def v5e_2x2():
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


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


# -- names a device trace reads ----------------------------------------------

def _shapes(*specs):
    return [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in specs]


KERNEL_NAMES = {
    "fused_irls_pallas": (
        lambda b, x, y, c: fused_irls_pallas(b, x, x, y, c, block_n=256,
                                             interpret=False),
        _shapes(((D,), jnp.float32), ((2, 512, D), jnp.float32),
                ((2, 512), jnp.float32), ((2,), jnp.int32))),
    "fused_irls_cv_pallas": (
        lambda b, x, y, c, f, o: fused_irls_cv_pallas(
            b, x, x, y, c, f, o, block_n=512, interpret=False),
        _shapes(((3, D), jnp.float32), ((2, 512, D), jnp.float32),
                ((2, 512), jnp.float32), ((2,), jnp.int32),
                ((2, 512), jnp.int32), ((3,), jnp.int32))),
    "gram_hessian_pallas": (
        lambda x, w: gram_hessian_pallas(x, w, block_n=256,
                                         interpret=False),
        _shapes(((512, D), jnp.float32), ((512,), jnp.float32))),
    "shamir_encode_share_pallas": (
        lambda x, c: shamir_encode_share_pallas(
            x, c, W, MODULI, 28, block_rows=8, interpret=False),
        _shapes(((8, 128), jnp.float64),
                ((len(MODULI), T - 1, 8, 128), jnp.uint32))),
    "shamir_poly_pallas": (
        lambda s, c: shamir_poly_pallas(s, c, W, MODULI[0], block_rows=8,
                                        interpret=False),
        _shapes(((8, 128), jnp.uint32), ((T - 1, 8, 128), jnp.uint32))),
    "shamir_reconstruct_pallas": (
        lambda s: shamir_reconstruct_pallas(
            s, lagrange_weights_host((1, 2), MODULI), MODULI, garner=True,
            block_rows=8, interpret=False),
        _shapes(((len(MODULI), T, 8, 128), jnp.uint32))),
}


def _on(chip, args):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        list(args))


@pytest.mark.parametrize("name", sorted(KERNEL_NAMES))
def test_kernel_launches_carry_their_names(name, one_chip):
    fn, args = KERNEL_NAMES[name]
    text = jax.jit(fn).lower(*_on(one_chip, args)).as_text()
    assert re.findall(r'kernel_name = "([^"]*)"', text) == [name]


def _fit_hlo(rung, key, sites, rows, dim, chip=None, slices=True):
    """The HLO of a two-round scan block of the ``rung`` summaries: as
    XLA compiles it for this host's device, or lowered for ``chip``,
    with the pack's slices of X as ``pack_partitions`` cuts them for the
    rung (``slices=False`` drops them: the float64 contractions)."""
    parts = generate_synthetic(jax.random.PRNGKey(key), num_institutions=sites,
                               records_per_institution=rows, dim=dim).parts
    packed = pack_partitions(list(parts), backend=rung)
    args = (jnp.zeros(dim, jnp.float64), jnp.asarray(np.inf),
            jnp.asarray(False), jnp.zeros((), jnp.int32),
            jax.random.PRNGKey(0), jnp.zeros((), jnp.int32),
            packed.X, packed.X32, packed.slices if slices else None,
            packed.y, packed.counts, jnp.asarray(1.0))
    lowered = fit_scan_block.lower(
        *(args if chip is None else _on(chip, args)),
        agg=SecureCollective(backend="pallas"), protect="both", l1=0.0,
        tol=1e-10, points=(1, 2), include_count=True,
        summaries_backend=rung, num_rounds=2, num_parts=sites,
        max_rounds=2)
    if chip is None:
        return lowered.compile().as_text()
    return lowered.compiler_ir("hlo").as_hlo_module().to_string()


@pytest.mark.parametrize("rung, on_chip", [
    ("pallas", False), ("reference", False), ("pallas", True)])
def test_fit_program_carries_every_scope(rung, on_chip, request,
                                         monkeypatch):
    """The fit program names every phase of a secure round, as
    ``bench/scopes.py`` reads it: on this host's ``pallas`` (simulated)
    and ``reference`` rungs, and the ``pallas`` rung lowered for the chip
    (the kernels compiled, not interpreted).  The host's rungs take the
    pre-cast Gram operand, so only the chip pads or casts per call
    (``summaries/operands``)."""
    if on_chip:
        chip = request.getfixturevalue("one_chip")
        monkeypatch.setattr(kernel_backend, "interpret_kernels",
                            lambda: False)
        monkeypatch.setattr(kernel_ops, "interpret_kernels", lambda: False)
        # shapes no other test traces, and the caches cleared after: no
        # caller on this host may reuse a trace that holds compiled kernels
        try:
            hlo = _fit_hlo(rung, 4, 2, 37, 5, chip)
        finally:
            jax.clear_caches()
    else:
        hlo = _fit_hlo(rung, 3, 3, 40, 4)
    index = hlo_index([hlo])
    found = {scope for entries in index.values() for _, scope in entries}
    # a part of ``summaries`` found is found under it
    want = set(SCOPES) - {"summaries"} - (
        set() if on_chip else {"summaries/operands"})
    assert want - found == set()
    if on_chip:
        # the summaries kernel's launch sits under summaries/gram
        assert {scope for entries in index.values()
                for text, scope in entries
                if "jit(fused_irls_pallas)" in text} == {"summaries/gram"}


def _f64_contractions_over_x(hlo, *shapes):
    """The dots and reduces of ``hlo`` with a float64 operand of one of
    ``shapes``, the payload's (S, N, d) and its pair design's: each
    operand's type is read from the instruction that defines it."""
    x_types = {"f64[" + ",".join(str(n) for n in shape) + "]"
               for shape in shapes}
    defined = re.compile(r"\s*(?:ROOT )?(%[\w.\-]+) = (\S+?)(?:\{| )")
    types = {m.group(1): m.group(2) for m in map(defined.match,
                                                   hlo.splitlines()) if m}
    found = []
    for line in hlo.splitlines():
        m = re.search(r" (dot|reduce)\(([^)]*)\)", line)
        if m and any(types.get(name) in x_types
                     for name in re.findall(r"%[\w.\-]+", m.group(2))):
            found.append(line.strip())
    return found


@pytest.mark.parametrize("rung", ["pallas", "reference"])
def test_chip_fit_program_contracts_no_float64_x(rung, one_chip,
                                                 monkeypatch):
    """The fit program lowered for the chip takes its float64 terms, and
    on the ``reference`` rung its float64 Gram, from the slices: no dot
    or reduce reads X, or X's pair design, as float64, which a TPU would
    emulate by re-splitting it every round.  Without the slices the same
    program holds such a contraction, so the check sees what it guards
    against."""
    monkeypatch.setattr(kernel_backend, "interpret_kernels", lambda: False)
    monkeypatch.setattr(kernel_ops, "interpret_kernels", lambda: False)
    sites, rows, dim = 3, 41, 6
    shapes = (sites, rows, dim), (sites, rows, dim * (dim + 1) // 2)
    try:
        sliced = _fit_hlo(rung, 5, sites, rows, dim, one_chip)
        plain = _fit_hlo(rung, 5, sites, rows, dim, one_chip,
                         slices=False)
    finally:
        jax.clear_caches()
    assert "bf16" in sliced
    assert _f64_contractions_over_x(sliced, *shapes) == []
    assert _f64_contractions_over_x(plain, *shapes) != []


def _pallas_sliced(beta, digits, scale, y, counts):
    """The ``pallas`` rung's float64 terms from the slices."""
    return sliced_terms(beta, XSlices(digits, scale), y, counts)


def _reference_sliced(beta, digits, scale, pair_digits, pair_scale, X, y,
                      counts):
    """The ``reference`` rung's summaries from the slices."""
    return _reference_summaries(
        beta, X, y, counts,
        XSlices(digits, scale, XSlices(pair_digits, pair_scale)))


@pytest.mark.parametrize("rung", ["pallas", "reference"])
def test_sliced_terms_keep_float64_out_of_their_dots(rung, one_chip):
    """At 64 sites of 3,760 rows the dots of the sliced terms, and on the
    ``reference`` rung the Gram's level dot too, each write their f32
    levels and nothing more.  Without the barrier XLA fused the float64
    level sum, an f32 pair, into the z dot at this shape, and on a v5e
    that program returned wrong z."""
    sites, rows = 64, 3760
    padded = -(-rows // SLAB) * SLAB
    dim = D if rung == "pallas" else 15
    slices = [((sites, padded, K * dim), jnp.bfloat16),
              ((sites, padded), jnp.float64)]
    fn = _pallas_sliced if rung == "pallas" else _reference_sliced
    if rung == "reference":
        slices += [((sites, padded, K * dim * (dim + 1) // 2), jnp.bfloat16),
                   ((sites, padded), jnp.float64),
                   ((sites, rows, dim), jnp.float64)]
    c = _compile(fn, one_chip, ((dim,), jnp.float64), *slices,
                 ((sites, rows), jnp.float64), ((sites,), jnp.int32))
    dots = [line for line in c.as_text().splitlines()
            if "kind=kOutput" in line and "dot_general" in line]
    assert len(dots) == (2 if rung == "pallas" else 3)
    assert all(re.match(r"\s*%[\w.\-]+ = f32\[", line) for line in dots)


def test_pod_fit_program_compiles_for_a_v5e_2x2(v5e_2x2, monkeypatch):
    """The fit with its institutions sharded over the four chips of a
    v5e 2x2, kernels compiled: each round sums the pods' share buffers
    with one all-reduce over the four devices, under ``pod_wire``, and
    the summaries kernel runs on every chip's own sites."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.distributed.sharding import POD_AXIS

    monkeypatch.setattr(kernel_backend, "interpret_kernels", lambda: False)
    monkeypatch.setattr(kernel_ops, "interpret_kernels", lambda: False)
    mesh = Mesh(np.array(v5e_2x2.devices), (POD_AXIS,))
    sites, rows, dim = 8, 300, 16  # two sites a chip
    padded = -(-rows // SLAB) * SLAB
    public = NamedSharding(mesh, P())
    split = NamedSharding(mesh, P(POD_AXIS))

    def arg(shape, dtype, sharding=public):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    key = jax.random.PRNGKey(0)
    try:
        with pod_partitioner(mesh):
            compiled = fit_scan_block.lower(
                arg((dim,), jnp.float64), arg((), jnp.float64),
                arg((), jnp.bool_), arg((), jnp.int32),
                arg(key.shape, key.dtype), arg((), jnp.int32),
                arg((sites, rows, dim), jnp.float64, split),
                arg((sites, rows, dim), jnp.float32, split),
                XSlices(arg((sites, padded, K * dim), jnp.bfloat16, split),
                        arg((sites, padded), jnp.float64, split)),
                arg((sites, rows), jnp.float64, split),
                arg((sites,), jnp.int32, split), arg((), jnp.float64),
                agg=SecureCollective(backend="pallas"), protect="both",
                l1=0.0, tol=1e-10, points=(1, 2), include_count=True,
                summaries_backend="pallas", num_rounds=2, num_parts=sites,
                max_rounds=2, mesh=mesh,
            ).compile()
    finally:
        jax.clear_caches()
    text = compiled.as_text()
    reduces = [line for line in text.splitlines()
               if re.search(r" all-reduce(-start)?\(", line)]
    assert len(reduces) == 1
    assert "replica_groups={{0,1,2,3}}" in reduces[0] or \
        "replica_groups=[1,4]<=[4]" in reduces[0]
    assert "/aggregate/pod_wire/" in reduces[0]
    assert "tpu_custom_call" in text
