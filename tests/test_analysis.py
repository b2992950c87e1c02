"""Tier-1 coverage for the static privacy gate (src/repro/analysis).

Pins, in order: every certified driver spec verifying clean with the
expected declassification trail; every leak fixture being CAUGHT with a
finding naming the offending equation path; the host-sync lint passing
on the real driver sources and failing on the legacy multi-readback
pattern; the host stopping-rule twins bit-matching the traced versions;
the headroom lint's pass/fail boundary; the mesh-axis allowlist; the
Pallas knob lint; and the callback census of the scan graphs.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.__main__ import _analyze_spec
from repro.analysis.drivers import all_driver_specs
from repro.analysis.fixtures import leak_fixture_specs
from repro.analysis.lints import (SummaryBounds, lint_headroom,
                                  lint_host_sync, lint_kernel_knobs,
                                  lint_mesh_axes, lint_no_callbacks)
from repro.analysis.report import AnalysisReport, Finding
from repro.analysis.taint import SECRET, iter_eqns

_SPECS = {s.name: s for s in all_driver_specs()}


# -- the certified surface -------------------------------------------------


@pytest.mark.parametrize("name", sorted(_SPECS))
def test_driver_certifies_clean(name):
    rep = _analyze_spec(_SPECS[name])
    assert rep.ok, rep.format(verbose=True)
    # every driver graph reveals something: the audit trail is non-empty
    assert rep.declassifications, f"{name}: no declassification recorded"


def test_gradient_mode_records_plaintext_declassification():
    """protect='gradient' routes H/deviance through the annotated
    declassify_sum — the audit trail must name it."""
    rep = _analyze_spec(_SPECS["secure_fit_fused[protect=gradient]"])
    assert any("declassify_sum" in d for d in rep.declassifications)
    assert any("_reveal_flat" in d for d in rep.declassifications)


def test_2d_mesh_uses_distributed_reveal():
    rep = _analyze_spec(_SPECS["secure_psum_2d"])
    assert any("_distributed_reveal" in d for d in rep.declassifications)


def test_pod_fit_sends_only_shares_across_chips():
    """The pod fit's graph: X, X32 and the slices enter SECRET on every
    pod (each pod's block of the sharded institution axis), every sum
    over the pod axis carries share material only, and the revealed sum
    is the one declassification."""
    from repro.distributed.sharding import POD_AXIS

    spec = _SPECS["pod_fit_scan[pods=4]"]
    closed, taints = spec.build()
    # X, X32, digits, scale, y and counts enter SECRET ...
    assert taints[6:] == [SECRET] * 6
    assert [v.aval.shape[0] for v in closed.jaxpr.invars[6:]] == [8] * 6
    # ... and each pod takes its own block of their institution axis
    (pod,) = [e for _, e, _ in iter_eqns(closed.jaxpr)
              if e.primitive.name == "shard_map"]
    split = [v.aval.shape for v, spec_ in zip(pod.invars,
                                               pod.params["in_specs"])
             if spec_ == (POD_AXIS,)]
    assert len(split) == 6 and all(s[0] == 8 for s in split)
    rep = _analyze_spec(spec)
    assert rep.ok, rep.format(verbose=True)
    assert rep.wire_operands
    assert all(w.endswith(("PROTECTED", "PROTECTED_AGG"))
               for w in rep.wire_operands), rep.wire_operands
    (only,) = rep.declassifications
    assert "jit(_reveal_flat)" in only


def test_pod_aggregate_headroom_counts_every_pods_institutions():
    """At a field where 52 addends fit the uint64 accumulator and 208 do
    not, the pod aggregate (52 institutions on each of 4 pods) refuses,
    and the same 52 on one device pass."""
    from jax.sharding import AbstractMesh, PartitionSpec as P

    from repro.core import SecureCollective
    from repro.core.collective import FlatProtected
    from repro.core.field import FieldSpec
    from repro.core.fixed_point import FixedPointCodec
    from repro.core.flatbuf import pack_pytree
    from repro.core.shamir import ShamirScheme
    from repro.distributed.sharding import POD_AXIS

    narrow = FieldSpec("narrow57", (2 ** 57 - 13,))
    assert 52 * narrow.moduli[0] < 2 ** 64 <= 208 * narrow.moduli[0]
    agg = SecureCollective(scheme=ShamirScheme(field=narrow),
                           codec=FixedPointCodec(field=narrow),
                           backend="pallas")
    _, layout = pack_pytree({"g": jnp.zeros((8,))})
    shares = jax.ShapeDtypeStruct((3, 1, 52, layout.rows, 128), jnp.uint32)

    def local(buf, axis_name=None):
        return agg.aggregate_batched(FlatProtected(buf, layout),
                                     axis_name).buf

    jax.eval_shape(local, shares)
    pods = jax.shard_map(
        lambda buf: local(buf, POD_AXIS),
        mesh=AbstractMesh((4,), (POD_AXIS,)), in_specs=P(),
        out_specs=P(), check_vma=False,
    )
    with pytest.raises(ValueError, match="cannot aggregate 208"):
        jax.eval_shape(pods, shares)


# -- negative controls -----------------------------------------------------


def _fixture(name):
    (spec,) = [s for s in leak_fixture_specs() if s.name == name]
    return _analyze_spec(spec, expect_leak=True)


def test_skip_protect_fixture_caught():
    rep = _fixture("LEAKY:skip_protect")
    assert not rep.ok
    assert any("outvars" in f.where and "SECRET" in f.message
               for f in rep.errors())


def test_reveal_slice_fixture_caught_at_the_reveal_eqn():
    """The acceptance case: a per-institution reveal is flagged with a
    finding naming the offending jaxpr equation path."""
    rep = _fixture("LEAKY:reveal_institution_slice")
    assert not rep.ok
    (f,) = [f for f in rep.errors() if "_reveal_flat" in f.where]
    assert "PER-INSTITUTION" in f.message
    assert "/eqn[" in f.where


def test_pod_plain_wire_fixture_caught_at_the_psum():
    """Plaintext summaries summed across pods: the finding names the
    collective that carries them."""
    rep = _fixture("LEAKY:pod_plain_wire")
    assert not rep.ok
    wire = [f for f in rep.errors() if f.where.endswith(":psum")]
    assert wire and all("only share buffers" in f.message for f in wire)
    assert all(w.endswith("SECRET") for w in rep.wire_operands)


def test_callback_fixture_caught_at_the_callback_eqn():
    rep = _fixture("LEAKY:callback_leak")
    assert not rep.ok
    assert any("debug_callback" in f.where for f in rep.errors())


# -- host-sync lint --------------------------------------------------------


def test_host_sync_lint_clean_on_repo_drivers():
    rep = lint_host_sync()
    assert rep.ok, rep.format(verbose=True)
    # one info finding per monitored method: the single marked sync
    infos = [f for f in rep.findings if f.severity == "info"]
    assert len(infos) == 5


_LEGACY_DRIVER = '''
import jax
import numpy as np

class Driver:
    def step_block(self):
        carry, objs, actives = fit_scan_block(self.beta)
        # host-sync: the block readback
        objs = jax.device_get(objs)
        # the legacy pattern: extra unmarked materializations, one per
        # carry element, each a separate device round-trip
        self._obj_prev = float(carry[1])
        self.converged = bool(carry[2])
        actives = np.asarray(actives)
        return objs
'''


def test_host_sync_lint_catches_legacy_multi_readback():
    rep = lint_host_sync(modules={
        "legacy.py": (_LEGACY_DRIVER, [("Driver", "step_block")]),
    })
    assert not rep.ok
    errs = rep.errors()
    # float(carry), bool(carry), np.asarray(actives): three stray syncs
    assert len(errs) == 3
    assert all("unannotated host materialization" in f.message
               for f in errs)
    assert any("float(carry)" in f.where for f in errs)


def test_host_sync_lint_requires_exactly_one_marked_site():
    doubled = _LEGACY_DRIVER.replace(
        "self._obj_prev = float(carry[1])",
        "# host-sync: a second one\n        "
        "self._obj_prev = float(carry[1])",
    ).replace("self.converged = bool(carry[2])", "pass") \
     .replace("actives = np.asarray(actives)", "pass")
    rep = lint_host_sync(modules={
        "doubled.py": (doubled, [("Driver", "step_block")]),
    })
    assert any("2 marked host-sync sites" in f.message
               for f in rep.errors())


# -- collective boundary-ownership lint ------------------------------------


def test_collective_sites_lint_clean_on_repo_tree():
    """The real package: every boundary call lives in an exempt file."""
    from repro.analysis.lints import lint_collective_sites

    rep = lint_collective_sites()
    assert rep.ok, rep.format(verbose=True)


def test_collective_sites_lint_flags_private_chain():
    """A driver growing its own protect -> reveal chain is an error;
    the same calls inside core/collective.py are the sanctioned owner."""
    from repro.analysis.lints import lint_collective_sites

    rogue = (
        "from repro.core.collective import _protect_flat, _reveal_flat\n"
        "def my_round(key, buf, scheme, frac_bits, rows, pts):\n"
        "    shares = _protect_flat(key, buf, scheme, frac_bits, rows)\n"
        "    return _reveal_flat(shares, scheme, frac_bits, pts)\n"
    )
    rep = lint_collective_sites(modules={"core/rogue.py": rogue})
    errs = rep.errors()
    assert len(errs) == 2
    assert all("outside core/collective.py" in f.message for f in errs)
    # identical source housed at the owner path is clean
    rep2 = lint_collective_sites(modules={"core/collective.py": rogue})
    assert rep2.ok


def test_collective_sites_lint_allows_imports_and_attributes():
    """Re-exports and attribute access don't build a chain — only calls
    (including method-style ``mod._reveal_flat(...)``) are flagged."""
    from repro.analysis.lints import lint_collective_sites

    compat = (
        "from .collective import _reveal_flat, _protect_flat\n"
        "SITES = ('_reveal_flat', '_distributed_reveal')\n"
        "handle = _reveal_flat\n"
    )
    rep = lint_collective_sites(modules={"core/compat.py": compat})
    assert rep.ok
    attr_call = (
        "from repro.core import collective\n"
        "out = collective._reveal_flat(b, s, f, p)\n"
    )
    rep2 = lint_collective_sites(modules={"selection/peek.py": attr_call})
    assert not rep2.ok


# -- stopping-rule host twins ----------------------------------------------


def test_should_stop_host_bitwise_matches_traced():
    from repro.core.newton import should_stop, should_stop_host

    grid = [0.0, 1e-12, 1e-6, 0.5, 1.0, 123.456, 1e12, np.inf]
    for prev in grid:
        for obj in [0.0, 1e-12, 0.4999, 123.456, 1e12, np.inf]:
            for tol, s, scale in [(1e-8, 3, 2.0 ** 28), (1e-4, 16, 8.0)]:
                dev = bool(should_stop(
                    jnp.float64(prev), jnp.float64(obj), tol, s, scale
                ))
                host = should_stop_host(prev, obj, tol, s, scale)
                assert dev == host, (prev, obj, tol, s, scale)


# -- headroom lint ---------------------------------------------------------


def test_headroom_lint_passes_deployment_envelope():
    rep = lint_headroom(SummaryBounds(d=128, n_max=100_000, num_parts=16))
    assert rep.ok, rep.format(verbose=True)
    infos = {f.where for f in rep.findings if f.severity == "info"}
    assert infos == {"aggregation", "codec"}


def test_headroom_lint_fails_past_codec_capacity():
    rep = lint_headroom(
        SummaryBounds(d=128, n_max=10 ** 9, num_parts=64)
    )
    assert not rep.ok
    assert any(f.where == "codec" for f in rep.errors())


def test_headroom_lint_fails_past_uint64_accumulator():
    rep = lint_headroom(
        SummaryBounds(d=4, n_max=10, num_parts=2 ** 35)
    )
    assert any(f.where == "aggregation" for f in rep.errors())


# -- mesh-axis lint --------------------------------------------------------


def test_mesh_axis_lint_flags_rogue_axis():
    from jax.sharding import AbstractMesh, PartitionSpec as P

    mesh = AbstractMesh((4,), ("rogue",))
    fn = jax.shard_map(lambda x: jax.lax.psum(x, "rogue"), mesh=mesh,
                       in_specs=(P(),), out_specs=P(), check_vma=False)
    closed = jax.make_jaxpr(fn)(jnp.ones((8,)))
    rep = lint_mesh_axes(closed, "rogue-test")
    assert not rep.ok
    assert any("unknown axis 'rogue'" in f.message for f in rep.errors())


def test_mesh_axis_lint_passes_protocol_axes():
    spec = _SPECS["secure_psum[sharded,tile]"]
    closed, _ = spec.build()
    rep = lint_mesh_axes(closed, spec.name)
    assert rep.ok, rep.format(verbose=True)


# -- Pallas knob lint ------------------------------------------------------


def test_kernel_knob_lint_default_knobs_fit_vmem():
    rep = lint_kernel_knobs()
    assert rep.ok
    assert len([f for f in rep.findings if f.severity == "info"]) == 4


def test_kernel_knob_lint_rejects_misaligned_block():
    from repro.kernels.tuning import DEFAULT_KNOBS

    knobs = dict(DEFAULT_KNOBS)
    knobs["fused_irls"] = knobs["fused_irls"].replace(block_n=7)
    rep = lint_kernel_knobs(knobs=knobs)
    assert not rep.ok
    assert any("block_n=7" in f.message for f in rep.errors())


def test_kernel_knob_lint_rejects_oversized_working_set():
    from repro.kernels.tuning import DEFAULT_KNOBS

    knobs = dict(DEFAULT_KNOBS)
    knobs["shamir_protect_flat"] = \
        knobs["shamir_protect_flat"].replace(block_rows=1 << 20)
    rep = lint_kernel_knobs(knobs=knobs)
    assert not rep.ok


# -- callback census -------------------------------------------------------


def test_scan_driver_graphs_are_callback_free():
    spec = _SPECS["secure_fit_scan[protect=both]"]
    closed, _ = spec.build()
    rep = lint_no_callbacks(closed, spec.name)
    assert rep.ok
    assert any("callback-free" in f.message for f in rep.findings)


def test_callback_census_flags_injected_callback():
    def fn(x):
        jax.debug.callback(lambda v: None, x)
        return x * 2.0

    closed = jax.make_jaxpr(fn)(jnp.ones((4,)))
    rep = lint_no_callbacks(closed, "injected")
    assert not rep.ok


# -- report plumbing -------------------------------------------------------


def test_report_dedup_and_severity_gate():
    rep = AnalysisReport(target="t")
    f = Finding("taint", "warning", "w", "m")
    rep.add(f)
    rep.add(f)
    assert len(rep.findings) == 1 and rep.ok
    rep.add(Finding("taint", "error", "w2", "m2"))
    assert not rep.ok and len(rep.errors()) == 1
    with pytest.raises(ValueError):
        Finding("taint", "fatal", "w", "m")
