"""Multi-host SPMD secure rounds: pod x share meshes, pipelined scans.

The single-process drivers (``SecureFitDriver``, ``StudyCoordinator``)
simulate every institution on one host.  This module is the launcher for
the real layout the paper describes: institutions laid along the
``POD_AXIS`` of a device mesh (one party per pod, ``secure_psum`` as the
wire) and — new here — the Computation Centers laid along a second
``SHARE_AXIS``, so each center-device only ever *holds* its own share
slice and the reveal itself is distributed.  Both wires — and the
``_distributed_reveal`` boundary itself — live on
:class:`repro.core.collective.SecureCollective` (``psum`` /
``psum_2d``); this module is the mesh/launcher layer around them:

* **1D (pod) mesh** — every device runs the full t-slice wire of
  :func:`repro.core.collective.secure_psum`; the scan-resident round
  chain (:func:`scan_secure_rounds`) keeps a whole block of rounds
  in-graph with the next round's sharing randomness generated while the
  current round's collective is in flight (double buffering: on a
  backend with async collectives + the latency-hiding scheduler the two
  overlap; on the CPU CI mesh it is the same math, just scheduled
  serially).
* **2D (pod, share) mesh** — :func:`secure_psum_2d`: each (pod i,
  share j) device evaluates institution i's sharing polynomial, keeps
  ONLY slice j, field-psums it over the pod axis (Algorithm 2, executed
  by center j), then the *reveal is a collective too*: each center
  scales its aggregated slice by its public Lagrange weight
  ``L_j(0) mod p_r`` and one exact uint64 psum over the share axis +
  trailing mod reconstructs the aggregate residues everywhere (CRT
  decode is local).  No device ever assembles another center's share —
  the wire moves exactly one slice per hop, matching the paper's trust
  model where centers jointly reveal only aggregates.

CI runs all of this on a forced-host-device CPU mesh
(``--xla_force_host_platform_device_count``, via
:mod:`repro.distributed.xla_flags` so the flag provably lands before jax
initializes); real multi-process runs call
:func:`initialize_distributed` first.
"""
from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp

from ..obs import trace as _trace
from .sharding import POD_AXIS, SHARE_AXIS, make_mesh

__all__ = [
    "SHARE_AXIS",
    "initialize_distributed",
    "pod_mesh",
    "pod_share_mesh",
    "secure_psum_2d",
    "scan_secure_rounds",
    "run_scanned_rounds",
]


def initialize_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> bool:
    """Gated ``jax.distributed.initialize``; no-op for single-process CI.

    Args default from the standard env vars (``JAX_COORDINATOR_ADDRESS``
    / ``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID``).  Returns True iff the
    distributed runtime was started — a forced-host-device CPU mesh in
    one process (the CI configuration) needs no runtime, so a plain
    ``num_processes in (None, 1)`` environment falls straight through.
    """
    if num_processes is None:
        num_processes = int(os.environ.get("JAX_NUM_PROCESSES", "1") or 1)
    if num_processes <= 1:
        return False
    jax.distributed.initialize(
        coordinator_address=coordinator_address
        or os.environ.get("JAX_COORDINATOR_ADDRESS"),
        num_processes=num_processes,
        process_id=process_id
        if process_id is not None
        else int(os.environ.get("JAX_PROCESS_ID", "0")),
    )
    return True


def pod_mesh(num_pods: int):
    """1D institution mesh: one party per device along ``POD_AXIS``."""
    return make_mesh((num_pods,), (POD_AXIS,))


def pod_share_mesh(num_pods: int, num_centers: int):
    """2D (pod, share) mesh: institutions x Computation Centers.

    ``num_centers`` is the reveal-subset size — normally the scheme
    threshold t, one device column per center that participates in the
    distributed reveal.
    """
    return make_mesh((num_pods, num_centers), (POD_AXIS, SHARE_AXIS))


def secure_psum_2d(tree, key, aggregator=None, dtype=jnp.float32,
                   pod_axis: str = POD_AXIS, share_axis: str = SHARE_AXIS,
                   points=None):
    """Secret-shared all-reduce on a 2D (pod, share) mesh.

    Call from inside ``shard_map`` over :func:`pod_share_mesh`.  The
    share-axis size must equal the reveal subset (default: the scheme
    threshold t).  Every (pod, share) device derives the SAME sharing
    polynomial for its pod (the rng folds only the pod index), keeps
    only its own slice, and the two collectives are

    1. uint64 psum over ``pod_axis``  — Algorithm 2 at center j;
    2. weighted uint64 psum over ``share_axis`` — the distributed
       Lagrange reveal (the ``_distributed_reveal`` boundary).

    Bit-equal to the 1D ``secure_psum`` wire: both reveal the exact
    field encoding of the global sum.  The chain itself is
    :meth:`repro.core.collective.SecureCollective.psum_2d`; this is the
    historical entry point.
    """
    from ..core.collective import SecureCollective

    agg = aggregator or SecureCollective(backend="pallas")
    return agg.psum_2d(tree, key, dtype=dtype, pod_axis=pod_axis,
                       share_axis=share_axis, points=points)


def scan_secure_rounds(tree, key, num_rounds: int, aggregator=None,
                       axis_name: str = POD_AXIS,
                       reveal: str = "replicated",
                       dtype=jnp.float32):
    """``num_rounds`` secure rounds as ONE in-graph ``lax.scan``.

    Call from inside ``shard_map`` over a 1D pod mesh.  Each round
    protects the current tree, field-all-reduces the t-slice share
    buffer over ``axis_name`` and reveals the aggregate; the revealed
    *mean* feeds the next round (a stand-in for the Newton update that
    keeps the round-to-round data dependency of the real fit).

    Double buffering: the sharing coefficients for round r+1 are drawn
    in the same scan step that reduces round r's shares — the two are
    data-independent, so a backend with async collectives and the
    latency-hiding scheduler (``LATENCY_HIDING_FLAGS``) overlaps the
    rng/encode work with the in-flight collective (request those flags
    via ``xla_flags.apply_xla_flags(latency_hiding=True)`` on GPU
    launches only — CPU builds abort on unknown ``--xla_gpu_*`` flags).
    Rounds use ``fold_in(key, slot)`` so the chain is bit-reproducible
    regardless of how many rounds one scan covers.
    """
    from ..core.collective import (
        REVEAL_MODES,
        SecureCollective,
        check_aggregation_headroom,
    )
    from ..core.field import random_elements_fast
    from ..core.flatbuf import LANES, pack_pytree, unpack_pytree
    from ..kernels import ops

    agg = aggregator or SecureCollective(backend="pallas")
    if agg.backend != "pallas":
        raise ValueError("scan_secure_rounds needs the flat-buffer wire")
    if reveal not in REVEAL_MODES:
        raise ValueError(f"reveal must be one of {REVEAL_MODES}")
    pts = agg._validated_points(None)
    scheme, field = agg.scheme, agg.scheme.field
    num_devices = jax.lax.axis_size(axis_name)
    check_aggregation_headroom(num_devices, field)
    key = agg.round_key(key, jax.lax.axis_index(axis_name))

    row_align = 8 if reveal == "replicated" else math.lcm(8, num_devices)
    buf0, layout = pack_pytree(tree, row_align=row_align)
    buf0 = buf0.astype(jnp.float64)

    def draw_coeffs(slot):
        return random_elements_fast(
            agg.round_key(key, slot),
            (scheme.threshold - 1, layout.rows, LANES), field,
        ).astype(jnp.uint32)

    def body(carry, _):
        buf, coeffs, slot = carry
        shares = ops.shamir_protect_flat(
            buf, coeffs, scheme.num_shares, field.moduli,
            agg.codec.frac_bits, points=pts,
        )
        if reveal == "replicated":
            summed = agg.allreduce(shares, axis_name)
            flat = agg.reveal_wire(summed, pts)
        else:
            tile = agg.allreduce(shares, axis_name, scatter_axis=2)
            flat_tile = agg.reveal_wire(tile, pts)
            flat = jax.lax.all_gather(
                flat_tile, axis_name, axis=0, tiled=True
            )
        # round r+1's sharing randomness: independent of the collective
        # above, so the latency-hiding scheduler may overlap them
        coeffs_next = draw_coeffs(slot)
        buf_next = flat / num_devices  # revealed mean -> next round input
        return (buf_next, coeffs_next, slot + 1), flat[0, 0]

    carry0 = (buf0, draw_coeffs(jnp.zeros((), jnp.int32)),
              jnp.ones((), jnp.int32))  # round 0's coeffs pre-drawn; the
    # in-scan draw at carry slot r produces round r's coeffs for the
    # next step, so executed round r always folds (key, r)
    (buf, _, _), trace = jax.lax.scan(body, carry0, None,
                                      length=num_rounds)
    return unpack_pytree(buf, layout, dtype=dtype), trace


def run_scanned_rounds(num_pods: int, tree, key, num_rounds: int,
                       aggregator=None, reveal: str = "replicated",
                       dtype=jnp.float32):
    """Host-level convenience: shard_map + jit around scan_secure_rounds.

    The input tree is replicated to every pod (each institution submits
    the same values, so round 1 reveals ``num_pods * tree`` and every
    later round preserves the mean — an easy invariant for tests and the
    round-latency benchmark).  Returns ``(final_tree, trace)``.
    """
    from jax.sharding import PartitionSpec as P

    mesh = pod_mesh(num_pods)
    fn = jax.jit(jax.shard_map(
        lambda: scan_secure_rounds(
            tree, key, num_rounds, aggregator=aggregator, reveal=reveal,
            dtype=dtype,
        ),
        mesh=mesh, in_specs=(), out_specs=P(), check_vma=False,
    ))
    with _trace.span("scan_block", "run_scanned_rounds",
                     num_pods=num_pods, num_rounds=num_rounds):
        return fn()
