"""Deliberately-leaky driver variants: the gate must FAIL on these.

Negative controls for ``scripts/static_checks.sh`` and
``tests/test_analysis.py``: each fixture is a small mutation of a real
driver round that commits one of the leak classes the taint verifier
exists to catch.  If the verifier ever certifies one of these, the gate
itself is broken — so the CLI runs them on every invocation and fails
unless every fixture produces an error finding.

* ``skip_protect``            — computes per-institution summaries and
  sums them with a plain (unannotated) ``jnp.sum``: SECRET data flows
  straight into the round's outputs (objective telemetry, beta).
* ``reveal_institution_slice``— protects correctly, then reveals ONE
  institution's share slice instead of the Algorithm-2 aggregate: the
  reconstruction is a per-institution summary.  The finding names the
  offending ``jit(_reveal_flat)`` equation path.
* ``callback_leak``           — ships a per-institution deviance into a
  ``jax.debug.callback`` (a print/telemetry hook): host code outside
  the protocol would observe institution-local data.
* ``pod_plain_wire``          — on a pod mesh, sums each pod's plaintext
  summaries across the pods with ``psum``: institution data crosses the
  wire between devices unprotected.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .drivers import DriverSpec, _aggregator, _packed
from .taint import PUBLIC, SECRET

__all__ = ["leak_fixture_specs"]


def _skip_protect_build():
    from ..core.batched_summaries import batched_local_summaries
    from ..core.batched_summaries import PackedPartitions
    from ..core.newton import newton_step, regularized_objective

    packed = _packed()
    beta = jnp.zeros((packed.dim,), jnp.float64)

    def fn(beta, X, X32, y, counts):
        sm = batched_local_summaries(
            beta, PackedPartitions(X, X32, y, counts),
            backend="pallas",
        )
        # LEAK: plain unannotated sums — no protect, no declassify_sum
        H = jnp.sum(sm.hessian, axis=0)
        g = jnp.sum(sm.gradient, axis=0)
        dev = jnp.sum(sm.deviance)
        obj = regularized_objective(dev, beta, 1.0)
        return newton_step(beta, H, g, 1.0), obj

    closed = jax.make_jaxpr(fn)(
        beta, packed.X, packed.X32, packed.y, packed.counts
    )
    return closed, [PUBLIC, SECRET, SECRET, SECRET, SECRET]


def _reveal_slice_build():
    from ..core.batched_summaries import batched_local_summaries
    from ..core.batched_summaries import PackedPartitions
    from ..core.secure_agg import FlatProtected

    agg = _aggregator()
    packed = _packed()
    beta = jnp.zeros((packed.dim,), jnp.float64)
    t = agg.scheme.threshold

    def fn(beta, key, X, X32, y, counts):
        sm = batched_local_summaries(
            beta, PackedPartitions(X, X32, y, counts),
            backend="pallas",
        )
        tree = {"gradient": sm.gradient, "deviance": sm.deviance}
        prot = agg.protect_batched(key, tree)
        # LEAK: slice institution 0's shares BEFORE Algorithm 2 — a
        # threshold reveal of this buffer reconstructs ONE institution's
        # summary, not the global aggregate
        inst0 = prot.buf[:t, :, 0]
        return agg.reveal(FlatProtected(inst0, prot.layout))

    closed = jax.make_jaxpr(fn)(
        beta, jax.random.PRNGKey(0), packed.X, packed.X32, packed.y,
        packed.counts,
    )
    return closed, [PUBLIC, PUBLIC, SECRET, SECRET, SECRET, SECRET]


def _callback_leak_build():
    from ..core.batched_summaries import batched_local_summaries
    from ..core.batched_summaries import PackedPartitions
    from ..core.newton import _fused_secure_iteration

    agg = _aggregator()
    packed = _packed()
    beta = jnp.zeros((packed.dim,), jnp.float64)

    def fn(beta, key, X, X32, slices, y, counts):
        sm = batched_local_summaries(
            beta, PackedPartitions(X, X32, y, counts, slices),
            backend="pallas",
        )
        # LEAK: per-institution deviances shipped to a host logging hook
        jax.debug.callback(lambda d: None, sm.deviance)
        return _fused_secure_iteration(
            beta, key, X, X32, slices, y, counts, 1.0, agg, "both", 0.0,
            summaries_backend="pallas",
        )

    closed = jax.make_jaxpr(fn)(
        beta, jax.random.PRNGKey(0), packed.X, packed.X32, packed.slices,
        packed.y, packed.counts,
    )
    return closed, [PUBLIC, PUBLIC] + [SECRET] * 6


def _pod_plain_wire_build():
    from jax.sharding import AbstractMesh, PartitionSpec as P

    from ..core.batched_summaries import batched_local_summaries
    from ..core.batched_summaries import PackedPartitions
    from ..core.newton import newton_step
    from ..distributed.sharding import POD_AXIS

    packed = _packed(num_parts=4)
    beta = jnp.zeros((packed.dim,), jnp.float64)

    def pod(beta, X, X32, y, counts):
        sm = batched_local_summaries(
            beta, PackedPartitions(X, X32, y, counts), backend="pallas",
        )
        # LEAK: the pods' plaintext sums cross the wire, no protect
        H = jax.lax.psum(jnp.sum(sm.hessian, axis=0), POD_AXIS)
        g = jax.lax.psum(jnp.sum(sm.gradient, axis=0), POD_AXIS)
        return newton_step(beta, H, g, 1.0)

    sites = P(POD_AXIS)
    fn = jax.shard_map(
        pod, mesh=AbstractMesh((4,), (POD_AXIS,)),
        in_specs=(P(), sites, sites, sites, sites), out_specs=P(),
        check_vma=False,
    )
    closed = jax.make_jaxpr(fn)(
        beta, packed.X, packed.X32, packed.y, packed.counts
    )
    return closed, [PUBLIC, SECRET, SECRET, SECRET, SECRET]


def leak_fixture_specs() -> list:
    """The negative controls, as DriverSpecs the same runner consumes."""
    t = _aggregator().scheme.threshold
    return [
        DriverSpec("LEAKY:skip_protect", _skip_protect_build, t),
        DriverSpec("LEAKY:reveal_institution_slice", _reveal_slice_build,
                   t),
        DriverSpec("LEAKY:callback_leak", _callback_leak_build, t),
        DriverSpec("LEAKY:pod_plain_wire", _pod_plain_wire_build, t),
    ]
