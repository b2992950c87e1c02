"""The one secure collective: pack -> protect -> aggregate -> reveal -> unpack.

The paper's entire protocol is a single primitive — institutions protect
local summaries, Computation Centers aggregate share-wise (Algorithm 2),
and only the threshold-met *aggregate* is ever reconstructed.  Before
this module the repo implemented that chain four near-identical times
(the host-side ``SecureAggregator`` rounds, the driver round bodies, the
selection sweep, and the in-SPMD ``secure_psum``/``secure_psum_2d``
wires).  :class:`SecureCollective` now owns the chain ONCE, with an
explicit axis for every way a consumer varies it:

* **batching** — :meth:`secure_round_batched` (S-leading institution
  batches) and :meth:`secure_round_multiconfig` ((config x institution)
  leading axes: the selection sweep's lambda x fold points, or the
  multi-study slot axis of :mod:`repro.core.multistudy`).
* **wire** — :meth:`psum` (1D pod-axis reduction of the flat uint32
  share buffer) and :meth:`psum_2d` (2D (pod, share) mesh where the
  reveal itself is a share-axis collective of Lagrange-weighted slices).
* **reveal placement** — ``reveal="replicated" | "sharded"`` and
  ``out="tree" | "tile"`` on the wire paths (:data:`REVEAL_MODES`,
  :data:`OUT_MODES`, :class:`ShardedAggregate`).
* **rng threading** — :meth:`round_key`: the ``fold_in(key, slot)``
  discipline every scan-resident consumer uses, so round r's sharing
  randomness is ``fold_in(key, r)`` regardless of block cutting.
* **byte telemetry** — :meth:`round_bytes`: the single static size
  model behind ``SecureFitDriver``, ``StudyCoordinator`` and the
  selection path's reports (previously three parallel accountings).
* **declassification sites** — the four named jit boundaries the static
  taint gate (:mod:`repro.analysis`) and the runtime privacy ledger
  (:mod:`repro.obs.ledger`) both key on live HERE and only here:
  ``_protect_flat``, ``_reveal_flat``, ``_distributed_reveal``,
  ``declassify_sum``.  A lint (``lint_collective_sites``) fails the gate
  if a direct call site appears outside this module, so the privacy
  review surface cannot silently grow back to four copies.

``repro.core.secure_agg`` remains the compatibility import surface
(``SecureAggregator`` is an alias of :class:`SecureCollective`); all
drivers and the SPMD wires route through this module.

Backends and the flat-buffer hot path
-------------------------------------
``backend="reference"`` walks the summary pytree leaf by leaf through
the uint64 jnp oracle — one dispatch per leaf per field op; it is the
bit-exactness oracle the flat wire is measured against.

``backend="pallas"`` runs the fused pipeline: the float pytree is packed
into ONE contiguous (rows, 128) tile buffer (`flatbuf.pack_pytree` —
pad once, remember the layout), so each phase is a single kernel launch
regardless of leaf count:

* ``protect``  — fused fixed-point encode + Horner share evaluation
  (`kernels.shamir_poly.shamir_encode_share_pallas`); the intermediate
  uint64 encoded tensor never materializes.  Returns a `FlatProtected`.
* ``aggregate`` — a streaming uint64 accumulator over the S submissions
  (exact sum, one trailing mod): no (S, ...) stack is ever allocated.
* ``reveal``   — fused Lagrange reconstruction + CRT Garner digit
  (`kernels.shamir_reconstruct`), then unpack back to the original
  pytree.

Share slices travel as uint32 (half the bytes of the reference uint64
path).  `FlatProtected` is a registered pytree whose only leaf is the
share buffer, so protocol code can slice/stack it with ``tree_map``
exactly like a plain share pytree.  All phases are jitted with the
layout/scheme as static arguments.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp

from ..distributed.sharding import POD_AXIS, SHARE_AXIS
from ..obs import ledger as _ledger
from ..obs.trace import traced as _traced
from .field import (
    FieldSpec,
    fsum,
    random_elements_fast,
)
from .fixed_point import FixedPointCodec
from .flatbuf import (
    FlatLayout,
    LANES,
    ROW_ALIGN,
    _rows_for,
    pack_pytree,
    pack_pytree_batched,
    unpack_pytree,
    unpack_pytree_tile,
)
from .shamir import ShamirScheme

__all__ = [
    "check_aggregation_headroom",
    "declassify_sum",
    "FlatProtected",
    "SecureCollective",
    "ShardedAggregate",
    "secure_psum",
    "REVEAL_MODES",
    "OUT_MODES",
]

REVEAL_MODES = ("replicated", "sharded")
OUT_MODES = ("tree", "tile")


def check_aggregation_headroom(num_addends: int, field: FieldSpec) -> None:
    """Guard the exact-uint64 share sum: ``S * max(p_r) < 2**64``.

    Every aggregation path (streaming fold, batched reduction, and the
    recombined limbs of the in-SPMD sum, :func:`_exact_psum`) accumulates
    reduced share elements (< p_r) in uint64 and applies ONE trailing
    mod, which is exact iff the unreduced sum cannot wrap.
    This is the single shared bound — ~2**33 institutions for the 31-bit
    moduli — enforced here so no path carries its own (historically
    inconsistent) claim.
    """
    if num_addends * max(field.moduli) >= 2**64:
        raise ValueError(
            f"cannot aggregate {num_addends} share tensors exactly: "
            f"{num_addends} * max modulus {max(field.moduli)} >= 2**64 "
            "would overflow the uint64 accumulator before the trailing mod"
        )


# ------------------------------------------------------------------------
# The four named declassification boundaries.  Each is a triple: an impl
# with a forced __name__/__qualname__ (the jit equation name the static
# taint verifier's rules match on), a jitted form, and a host wrapper
# that records to the runtime privacy ledger before dispatching.  These
# are the ONLY direct call sites of the boundary wrappers in the tree
# (enforced by ``repro.analysis.lints.lint_collective_sites``).
# ------------------------------------------------------------------------


def _declassify_sum_impl(x, axis: int = 0):
    return jnp.sum(x, axis=axis)


# the jit equation must be NAMED declassify_sum — that exact name is the
# key the static taint verifier's declassification rules match on
_declassify_sum_impl.__name__ = "declassify_sum"
_declassify_sum_impl.__qualname__ = "declassify_sum"
_declassify_sum_jit = functools.partial(
    jax.jit, static_argnames=("axis",)
)(_declassify_sum_impl)


def declassify_sum(x, axis: int = 0):
    """The sanctioned PLAINTEXT aggregation over the institution axis.

    Semantically just ``jnp.sum(x, axis=axis)`` — but spelled as a named
    jitted boundary so the static privacy-flow verifier
    (:mod:`repro.analysis`) can certify it.  The paper's pragmatic
    protect modes ("gradient" / "hessian" / "none") deliberately exchange
    SOME summaries in the clear; the protocol contract is that only
    their *cross-institution sums* ever leave the round.  Every driver
    spells those sums through this function, which the taint verifier
    treats as the one annotated SECRET -> PUBLIC declassification for
    unprotected leaves (it still checks the reduction actually
    aggregates >= 2 addends, so a non-reducing "sum" cannot launder an
    individual institution's summary).  A plain ``jnp.sum`` on secret
    data fails the gate — which is the point: intentional plaintext
    aggregation must be visible and auditable.

    The runtime privacy-audit ledger (:mod:`repro.obs.ledger`) counts
    every *Python-level invocation* of this boundary: the hook lives in
    this host wrapper, outside the jitted body, so a host-level call
    records once per call (per round in the loop drivers) and a call
    inside an enclosing ``jit`` records once per call site each time
    the enclosing graph is traced.  Cached dispatches of an already
    certified graph add no new declassification sites by construction —
    ``python -m repro.obs audit`` reconciles the recorded counts against
    a per-equation census of each driver spec's graph.  The hook records
    static metadata only (shape/axis), never values, and adds no
    equation to the graph.
    """
    _ledger.record_site("declassify_sum", what=f"axis{axis}_sum",
                        shape=x.shape)
    return _declassify_sum_jit(x, axis=axis)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class FlatProtected:
    """Protected flat-buffer representation: one uint32 share tensor.

    ``buf`` is (w, R, rows, 128) fresh from ``protect`` (holder axis
    leading), (R, rows, 128) after per-center slicing, or (k, R, rows, 128)
    once >= t centers stack their aggregate slices for reveal.  ``layout``
    (static aux data) remembers how to unpack the revealed buffer back into
    the original pytree.  Registered as a pytree so protocol-level
    ``tree_map`` slicing/stacking works transparently.
    """

    buf: jnp.ndarray
    layout: FlatLayout

    def tree_flatten(self):
        return (self.buf,), self.layout

    @classmethod
    def tree_unflatten(cls, layout, children):
        return cls(children[0], layout)


@functools.partial(
    jax.jit, static_argnames=("field", "residue_axis")
)
def _fsum_batched(stacked, field: FieldSpec, residue_axis: int):
    """Jitted S-way field reduction (cast + sum + mod fused by XLA)."""
    return fsum(stacked, field, axis=0, residue_axis=residue_axis)


@functools.partial(
    jax.jit, static_argnames=("field", "residue_axis")
)
def _fold_sum_streaming(submissions, field: FieldSpec, residue_axis: int):
    """Share-wise sum of S submissions WITHOUT materializing an S-stack.

    A running uint64 accumulator folds the submissions one by one with a
    single mod at the end — exact iff ``S * max(p_r) < 2**64``, the shared
    bound ``check_aggregation_headroom`` enforces on every caller.  XLA
    fuses the unrolled chain into one elementwise loop over donation-sized
    buffers, so peak memory is one accumulator — not the (S, ...) stack
    the eager ``jnp.stack`` reduction allocated, which at 1e6+ params made
    ``aggregate`` allocation-bound.
    """
    acc = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.uint64), submissions[0]
    )
    for nxt in submissions[1:]:
        acc = jax.tree_util.tree_map(
            lambda a, b: a + b.astype(jnp.uint64), acc, nxt
        )

    def _reduce(a, orig):
        p = field._bcast(a, residue_axis)
        return (a % p).astype(orig.dtype)

    return jax.tree_util.tree_map(_reduce, acc, submissions[0])


def _protect_flat_impl(key, buf, scheme: ShamirScheme, frac_bits: int,
                       rows: int, points: tuple[int, ...] | None = None):
    from ..kernels import ops

    field = scheme.field
    coeffs = random_elements_fast(
        key, (scheme.threshold - 1, rows, LANES), field
    ).astype(jnp.uint32)  # (R, t-1, rows, 128)
    return ops.shamir_protect_flat(
        buf, coeffs, scheme.num_shares, field.moduli, frac_bits,
        points=points,
    )  # (len(points) or w, R, rows, 128) uint32


# keep the jit names the taint verifier's declassification rules key on
_protect_flat_impl.__name__ = "_protect_flat"
_protect_flat_impl.__qualname__ = "_protect_flat"
_protect_flat_jit = functools.partial(
    jax.jit, static_argnames=("scheme", "frac_bits", "rows", "points")
)(_protect_flat_impl)


def _protect_flat(key, buf, scheme: ShamirScheme, frac_bits: int, rows: int,
                  points: tuple[int, ...] | None = None):
    """Host wrapper: ledger hook + the jitted protect boundary.

    The audit ledger records per Python-level invocation (see
    :func:`declassify_sum` for the counting semantics).
    """
    _ledger.record_site("_protect_flat", what="encode+share",
                        shape=buf.shape, threshold=scheme.threshold)
    return _protect_flat_jit(key, buf, scheme, frac_bits, rows,
                             points=points)


def _reveal_flat_impl(buf, scheme: ShamirScheme, frac_bits: int,
                      points: tuple[int, ...]):
    from ..kernels import ops

    return ops.shamir_reveal_flat(
        buf, points, scheme.field.moduli, frac_bits,
    )  # (rows, 128) float64


_reveal_flat_impl.__name__ = "_reveal_flat"
_reveal_flat_impl.__qualname__ = "_reveal_flat"
_reveal_flat_jit = functools.partial(
    jax.jit, static_argnames=("scheme", "frac_bits", "points")
)(_reveal_flat_impl)


def _reveal_flat(buf, scheme: ShamirScheme, frac_bits: int,
                 points: tuple[int, ...]):
    """Host wrapper: ledger hook + the jitted reveal boundary.

    Every reveal — certified in-graph call sites AND any stray
    host-level call — passes through here, so the runtime audit counts
    it even when the jitted impl hits the compilation cache.
    """
    _ledger.record_site("_reveal_flat", what="lagrange_reveal",
                        shape=buf.shape, threshold=scheme.threshold)
    return _reveal_flat_jit(buf, scheme, frac_bits, points)


def _distributed_reveal_impl(agg_slice, scheme, codec, points, share_axis,
                             dtype):
    """Lagrange reconstruction as a SHARE_AXIS collective.

    ``agg_slice`` is this center's aggregated share slice (R, rows, 128)
    uint32.  Each center multiplies by its own public weight
    ``L_j(0) mod p_r`` (field mul, uint64), then ONE exact sum over the
    share axis (:func:`_exact_psum`) + trailing mod yields the aggregate
    residues — the k partial products are each < p_r < 2**31.  CRT
    decode is local.

    Jitted under its own name on purpose: the static privacy-flow gate
    (:mod:`repro.analysis`) recognizes the ``_distributed_reveal`` jit
    as the 2D mesh's ONE sanctioned declassification and checks its
    operand is the pod-aggregated share slice revealed over a
    threshold-satisfying share axis.
    """
    from .field import crt_combine_signed
    from .shamir import lagrange_coeffs_at_zero

    field = scheme.field
    lam = lagrange_coeffs_at_zero(points, field)  # (R, k) uint64
    j = jax.lax.axis_index(share_axis)
    w = jnp.take(lam, j, axis=1)  # (R,) this center's weight
    partial = (agg_slice.astype(jnp.uint64) * w[:, None, None]) \
        % field._bcast(agg_slice, 0)
    summed = _exact_psum(partial, share_axis) % field._bcast(partial, 0)
    signed = crt_combine_signed(summed, field)
    return (signed.astype(jnp.float64) / codec.scale).astype(dtype)


# the jit equation must keep the exact name the static gate's
# declassification rules match on
_distributed_reveal_impl.__name__ = "_distributed_reveal"
_distributed_reveal_impl.__qualname__ = "_distributed_reveal"
_distributed_reveal_jit = functools.partial(
    jax.jit, static_argnames=("scheme", "codec", "points", "share_axis",
                              "dtype")
)(_distributed_reveal_impl)


def _distributed_reveal(agg_slice, scheme, codec, points, share_axis,
                        dtype):
    """Host wrapper: privacy-ledger hook + the jitted collective reveal.

    The runtime audit counts per Python-level invocation — once per
    trace of the enclosing ``shard_map`` graph (see
    :func:`declassify_sum` for semantics).
    """
    _ledger.record_site("_distributed_reveal", what="share_axis_reveal",
                        shape=agg_slice.shape,
                        threshold=scheme.threshold)
    return _distributed_reveal_jit(agg_slice, scheme, codec, points,
                                   share_axis, dtype)


# limb width of the exact cross-device sum: D addends of a 16-bit limb
# stay below 2**32 for D < 2**16 devices
_LIMB_BITS = 16


def _exact_psum(x, axis_name: str, scatter_axis: int | None = None):
    """Exact uint64 sum over a mesh axis of field elements (< 2**31).

    XLA:TPU has no 64-bit collectives, and a uint32 sum of a few elements
    near 2**31 wraps.  Each element therefore travels as two 16-bit limbs
    in uint32 — the same 8 bytes per element a uint64 wire moves — whose
    sums stay exact for fewer than 2**16 devices, and the limbs recombine
    locally in uint64.  ``scatter_axis`` reduce-scatters that axis of
    ``x`` (tiled) instead of all-reducing.
    """
    num_devices = jax.lax.axis_size(axis_name)
    if num_devices >= 1 << _LIMB_BITS:
        raise ValueError(
            f"cannot sum over {num_devices} devices exactly: the 16-bit "
            "limb sums would overflow uint32"
        )
    x = x.astype(jnp.uint32)
    mask = jnp.uint32((1 << _LIMB_BITS) - 1)
    limbs = jnp.stack([x & mask, x >> jnp.uint32(_LIMB_BITS)])
    if scatter_axis is None:
        summed = jax.lax.psum(limbs, axis_name)
    else:
        summed = jax.lax.psum_scatter(
            limbs, axis_name, scatter_dimension=scatter_axis + 1, tiled=True,
        )
    return summed[0].astype(jnp.uint64) \
        + (summed[1].astype(jnp.uint64) << jnp.uint64(_LIMB_BITS))


def _field_allreduce(shares, axis_name: str, field: FieldSpec,
                     residue_axis: int = 1, scatter_axis: int | None = None):
    """Exact share-wise field sum over a mesh axis (Algorithm 2 on the wire).

    XLA's collective has no per-hop modular reduction, so the sum is
    taken exactly (:func:`_exact_psum`) and a single trailing mod returns
    the reduced wire dtype.  A deployment fabric doing per-hop modular
    adds would move the reduced uint32 elements instead; the payload
    accounting counts those (see ``benchmarks/secure_psum.py``).

    ``scatter_axis=None`` all-reduces (every device gets the full summed
    buffer); an integer reduce-scatters that axis so each device keeps
    only its 1/D tile of the distributed residues.
    """
    summed = _exact_psum(shares, axis_name, scatter_axis)
    return (summed % field._bcast(summed, residue_axis)).astype(shares.dtype)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ShardedAggregate:
    """A revealed aggregate that STAYS sharded over the reduce axis.

    ``secure_psum(reveal="sharded", out="tile")`` hands every device its
    decoded ``(rows / D, 128)`` plaintext tile of the flat aggregate
    buffer instead of all-gathering + unpacking.  Downstream code that
    consumes the aggregate shard-wise (a distributed solve, a sharded
    optimizer update) skips the gather entirely; anything that needs the
    whole tree calls :meth:`gather` — which is exactly what
    ``out="tree"`` would have done, so the two spellings are bit-equal.

    Registered as a pytree with the tile as its only leaf (layout and
    tile count are static aux data), so it crosses ``shard_map`` /
    ``jit`` boundaries like a plain array.
    """

    tile: jnp.ndarray
    layout: FlatLayout
    num_tiles: int

    def gather(self, axis_name: str, dtype=jnp.float32):
        """All-gather the plaintext tiles and unpack the full pytree."""
        flat = jax.lax.all_gather(self.tile, axis_name, axis=0, tiled=True)
        return unpack_pytree(flat, self.layout, dtype=dtype)

    def local_fragments(self, tile_index: int, dtype=None):
        """Leaf fragments in THIS tile (static ``tile_index`` required).

        See :func:`repro.core.flatbuf.unpack_pytree_tile` for the
        ``{leaf: (start, stop, fragment)}`` contract.
        """
        return unpack_pytree_tile(
            self.tile, self.layout, tile_index, self.num_tiles, dtype=dtype
        )

    def tree_flatten(self):
        return (self.tile,), (self.layout, self.num_tiles)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(leaves[0], *aux)


@dataclasses.dataclass(frozen=True)
class SecureCollective:
    """The one protect -> aggregate -> reveal pipeline for float pytrees.

    ``backend=None`` inherits the scheme's backend; passing "pallas" or
    "reference" overrides the scheme to match (convenience so callers can
    write ``SecureCollective(backend="pallas")``).

    ``overflow_check=True`` arms the debug-mode fixed-point overflow
    assert on every protect path: a value past the capacity bound raises
    ``OverflowError`` (eagerly outside jit, at the next sync inside)
    instead of silently saturating into a plausible-but-wrong reveal —
    the hard-failure form of the ``headroom_ok`` predicate.  Paths that
    know the addend count (``protect_batched`` over S institutions,
    ``psum`` over D devices) tighten the bound to ``capacity / S`` so an
    aggregate that would overflow is caught at protect time, not
    revealed wrong.

    Every secure driver routes here: the fused/scanned fit rounds via
    :meth:`secure_round_batched`, the selection sweep (and the
    multi-study slot packing) via :meth:`secure_round_multiconfig`, the
    SPMD wires via :meth:`psum` / :meth:`psum_2d`, and the scan-resident
    wire via :meth:`allreduce` + :meth:`reveal_wire`.  Byte telemetry
    for all of them comes from :meth:`round_bytes`.
    """

    scheme: ShamirScheme = ShamirScheme()
    codec: FixedPointCodec = FixedPointCodec()
    backend: str | None = None
    overflow_check: bool = False

    def __post_init__(self):
        if self.backend is None:
            object.__setattr__(self, "backend", self.scheme.backend)
        elif self.backend != self.scheme.backend:
            object.__setattr__(
                self, "scheme",
                dataclasses.replace(self.scheme, backend=self.backend),
            )
        if self.scheme.field is not self.codec.field and (
            self.scheme.field.moduli != self.codec.field.moduli
        ):
            raise ValueError("scheme and codec must agree on the field")

    # rng threading --------------------------------------------------------
    @staticmethod
    def round_key(key: jax.Array, slot) -> jax.Array:
        """The one rng-threading rule: round r's key is ``fold_in(key, r)``.

        Every scan-resident consumer (``fit_scan_block``, the selection
        sweep, ``scan_secure_rounds``) folds the protect rng in-graph
        from a single key and the round slot, so executed round r always
        sees the same sharing randomness regardless of how the fit was
        cut into blocks — which is what makes ``state_dict`` resume
        bit-identical to an uninterrupted run.
        """
        return jax.random.fold_in(key, slot)

    # institution side --------------------------------------------------------
    @_traced("protect")
    def protect(self, key: jax.Array, tree):
        """Encode floats to the field and split into shares.

        Reference backend: per-leaf share pytree of (w, R, ...) uint64.
        Pallas backend: a single ``FlatProtected`` share buffer.
        """
        if self.backend == "pallas":
            buf, layout = pack_pytree(tree)
            if self.overflow_check:
                self.codec.check_headroom(buf, what="protect")
            shares = _protect_flat(
                key, buf, self.scheme, self.codec.frac_bits, layout.rows
            )
            return FlatProtected(shares, layout)
        encoded = jax.tree_util.tree_map(
            functools.partial(self.codec.encode, check=self.overflow_check),
            tree,
        )
        return self.scheme.share_pytree(key, encoded)

    @_traced("protect")
    def protect_batched(self, key: jax.Array, tree):
        """Protect S institutions' summaries in ONE kernel launch.

        ``tree`` leaves carry a leading S (institution) axis; the S flat
        slices are packed side by side and pushed through a single
        encode+share launch.  Returns a ``FlatProtected`` whose buffer is
        (w, R, S, rows, 128) — feed it to ``aggregate_batched`` to reduce
        the S axis (the layout describes one slice, i.e. the aggregate).
        Pallas backend only: the batched layout IS the flat wire format.
        """
        if self.backend != "pallas":
            raise ValueError("protect_batched requires the pallas backend")
        buf, layout = pack_pytree_batched(tree)
        if self.overflow_check:
            # the S slices will be summed: bound each by capacity / S so
            # the AGGREGATE cannot overflow (the headroom_ok contract)
            self.codec.check_headroom(
                buf, num_addends=buf.shape[0], what="protect_batched"
            )
        s_dim, rows = buf.shape[0], layout.rows
        shares = _protect_flat(
            key, buf.reshape(s_dim * rows, LANES), self.scheme,
            self.codec.frac_bits, s_dim * rows,
        )  # (w, R, S*rows, 128)
        w, num_r = shares.shape[0], shares.shape[1]
        return FlatProtected(
            shares.reshape(w, num_r, s_dim, rows, LANES), layout
        )

    # computation-center side -------------------------------------------------
    @_traced("aggregate")
    def aggregate(self, protected: Sequence):
        """Share-wise sum over institutions (still protected).

        Streams a running uint64 accumulator over the S submissions (one
        fused elementwise chain, single mod) instead of stacking them: at
        1e6+ params the old eager ``jnp.stack`` made this phase
        allocation-bound on the (S, w, R, ...) stack.
        """
        if not protected:
            raise ValueError("nothing to aggregate")
        if len(protected) == 1:
            return protected[0]
        field = self.scheme.field
        check_aggregation_headroom(len(protected), field)
        # leaves are (w, R, ...) protect outputs: residue axis 1 (same
        # contract as secure_add)
        return _fold_sum_streaming(tuple(protected), field, residue_axis=1)

    @_traced("aggregate")
    def aggregate_batched(self, protected: FlatProtected) -> FlatProtected:
        """Reduce the institution axis of a ``protect_batched`` output.

        One exact uint64 reduction over axis 2 of the (w, R, S, rows, 128)
        share buffer — Algorithm 2 for all S submissions in a single
        dispatch, with no per-submission stacking step.
        """
        check_aggregation_headroom(protected.buf.shape[2], self.scheme.field)
        buf = fsum(protected.buf, self.scheme.field, axis=2, residue_axis=1)
        return FlatProtected(buf, protected.layout)

    def allreduce(self, shares, axis_name: str, residue_axis: int = 1,
                  scatter_axis: int | None = None):
        """Algorithm 2 over a mesh axis: exact field psum of share slices.

        The in-SPMD aggregation step of the wire paths; see
        :func:`_field_allreduce` for the exactness argument.
        """
        return _field_allreduce(shares, axis_name, self.scheme.field,
                                residue_axis=residue_axis,
                                scatter_axis=scatter_axis)

    def _validated_points(self, points) -> tuple[int, ...]:
        """Normalize + sanity-check reveal points (1-based, distinct).

        ``None`` defaults to the first t points — the SAME t-subset
        default every reveal path uses (reconstruction from any t shares
        is exact, so a t-subset reveal is bit-identical to the all-w one
        and does strictly less work).  Below-threshold subsets are
        rejected here, before any reduction over a short share axis.
        """
        w = self.scheme.num_shares
        if points is None:
            points = tuple(range(1, self.scheme.threshold + 1))
        points = tuple(int(p) for p in points)
        if any(not (1 <= p <= w) for p in points):
            raise ValueError(f"points must be in 1..{w}, got {points}")
        if len(set(points)) != len(points):
            raise ValueError(f"points must be distinct, got {points}")
        if len(points) < self.scheme.threshold:
            raise ValueError(
                f"need >= t={self.scheme.threshold} shares, got "
                f"{len(points)} (information-theoretically irrecoverable "
                "below threshold)"
            )
        return points

    @_traced("secure_round")
    def secure_round_batched(self, key: jax.Array, tree,
                             points: Sequence[int] | None = None,
                             dtype=jnp.float64):
        """One whole Algorithm-1+2 round over S-leading summaries.

        protect_batched (ONE encode+share launch) -> aggregate_batched
        (single exact uint64 reduction over the institution axis) ->
        reveal of the *global* aggregate from the ``points`` centers'
        slices.  ``points`` are the 1-based evaluation points of the
        centers participating in the reveal (default: the first t); a
        short list raises the below-threshold error from ``reveal``, so a
        caller that lost too many centers fails loudly instead of
        reducing over a short share axis.  Fully traceable — this is the
        round helper both the fused ``secure_fit`` iteration and the
        fused ``StudyCoordinator.step`` run inside one jitted graph.
        """
        points = self._validated_points(points)
        prot = self.protect_batched(key, tree)
        aggd = self.aggregate_batched(prot)
        sel = jnp.asarray([p - 1 for p in points])
        return self.reveal(
            FlatProtected(aggd.buf[sel], aggd.layout), points=points,
            dtype=dtype,
        )

    @_traced("secure_round")
    def secure_round_multiconfig(self, key: jax.Array, tree,
                                 points: Sequence[int] | None = None,
                                 dtype=jnp.float64):
        """One secure round over a (C, S, ...)-leading summary tree.

        The slot-packed wire shape: every leaf carries a leading
        (config, institution) pair of axes.  For the selection sweep the
        C axis is the (lambda x fold) path points advancing together;
        for the multi-study server seed (:mod:`repro.core.multistudy`)
        it is the study slot — independent cohorts advanced by one
        round.  The whole round is still three launches total,
        independent of C:

        * ONE encode+share launch over the (C * S) flat slices
          (``protect_batched`` on the collapsed leading axis),
        * ONE exact uint64 reduction over the institution axis — the
          share buffer reshapes to (w, R, C, S, rows, 128) and Algorithm
          2 runs per config along axis 3,
        * ONE Lagrange+CRT reveal over the (C * rows, 128) stack of
          per-config aggregates, unpacked back to (C, ...)-leading
          leaves.

        Per-institution validation scores therefore never exist in the
        clear anywhere: held-out metrics enter as shares and only their
        cross-institution sums are reconstructed, per config.  Fully
        traceable; this runs inside the selection scan's jitted graph.
        """
        points = self._validated_points(points)
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if not leaves:
            raise ValueError("cannot run a round on an empty pytree")
        c_dim, s_dim = leaves[0].shape[0], leaves[0].shape[1]
        if any(l.shape[:2] != (c_dim, s_dim) for l in leaves):
            raise ValueError(
                "all leaves need the same leading (config, institution) axes"
            )
        flat_tree = jax.tree_util.tree_unflatten(
            treedef,
            [l.reshape((c_dim * s_dim,) + l.shape[2:]) for l in leaves],
        )
        prot = self.protect_batched(key, flat_tree)
        w, num_r, _, rows, lanes = prot.buf.shape
        by_config = prot.buf.reshape(w, num_r, c_dim, s_dim, rows, lanes)
        # Algorithm 2 per config: exact uint64 reduction over institutions
        check_aggregation_headroom(s_dim, self.scheme.field)
        aggd = fsum(by_config, self.scheme.field, axis=3, residue_axis=1)
        sel = jnp.asarray([p - 1 for p in points])
        stacked = aggd[sel].reshape(len(points), num_r, c_dim * rows, lanes)
        flat = _reveal_flat(
            stacked, self.scheme, self.codec.frac_bits, points
        )  # (C * rows, 128) float64
        from .flatbuf import unpack_pytree_batched

        return unpack_pytree_batched(
            flat.reshape(c_dim, rows, lanes), prot.layout, dtype=dtype
        )

    @_traced("reveal")
    def reveal(self, protected, points=None, dtype=jnp.float64):
        """Joint reconstruction of the (aggregate) secret -> floats.

        In deployment this is the only step that requires >= t centers to
        cooperate, and it is only ever invoked on *global* aggregates.

        ``points=None`` assumes the share slices are in holder order
        (1..k, as ``protect`` emits them) and reconstructs from the first
        t — the unified ``_validated_points`` default on BOTH backends.
        Reconstruction from any t-subset is exact field arithmetic, so the
        result is bit-identical to an all-k reveal at a fraction of the
        Lagrange work.  Pass explicit ``points`` when the slices are a
        non-contiguous center subset (then they must match the slice
        count).
        """
        t = self.scheme.threshold
        if isinstance(protected, FlatProtected):
            k = protected.buf.shape[0]
            if k < t:
                raise ValueError(
                    f"need >= t={t} shares, got {k} "
                    "(information-theoretically irrecoverable below "
                    "threshold)"
                )
            if points is None:
                buf = protected.buf[:t] if k > t else protected.buf
                pts = self._validated_points(None)
            else:
                buf = protected.buf
                pts = self._validated_points(points)
                if len(pts) != k:
                    raise ValueError("points must match share count")
            flat = _reveal_flat(
                buf, self.scheme, self.codec.frac_bits, pts
            )
            return unpack_pytree(flat, protected.layout, dtype=dtype)
        if points is None:
            # same t-subset default as the flat path: slice each leaf's
            # holder axis down to the first t shares before reconstructing
            leaves = jax.tree_util.tree_leaves(protected)
            k = leaves[0].shape[0] if leaves else 0
            if k < t:
                raise ValueError(
                    f"need >= t={t} shares, got {k} "
                    "(information-theoretically irrecoverable below "
                    "threshold)"
                )
            protected = jax.tree_util.tree_map(
                lambda s: s[:t], protected
            )
            points = self._validated_points(None)
        recon = self.scheme.reconstruct_pytree(protected, list(points))
        return jax.tree_util.tree_map(
            lambda v: self.codec.decode(v, dtype=dtype), recon
        )

    def reveal_wire(self, buf, points: tuple[int, ...]):
        """Reveal a raw (k, R, rows, 128) aggregated share buffer in-graph.

        The wire-level reveal entry for scan-resident consumers
        (``distributed.multihost.scan_secure_rounds``) that carry the
        flat buffer themselves instead of a ``FlatProtected``: Lagrange
        + CRT decode to a (rows, 128) float64 tile.  Exists so the
        ``_reveal_flat`` boundary is only ever invoked from this module
        (the ``lint_collective_sites`` contract); semantics are exactly
        :func:`_reveal_flat`.
        """
        return _reveal_flat(buf, self.scheme, self.codec.frac_bits, points)

    def headroom_ok(self, max_abs: float, num_institutions: int) -> bool:
        """True if S summaries of magnitude <= max_abs aggregate exactly."""
        return max_abs * num_institutions < self.codec.capacity()

    # byte telemetry ----------------------------------------------------------
    def round_bytes(self, d: int, num_parts: int, protect: str,
                    include_count: bool = False,
                    num_live_centers: int | None = None,
                    num_configs: int = 1, extra_scalars: int = 0) -> int:
        """Per-round wire bytes from static shapes/dtypes alone.

        The ONE size model behind every driver's telemetry
        (``SecureFitDriver``, ``StudyCoordinator.reports``, the selection
        path's ``bytes_per_round`` — previously three parallel
        accountings).  Every round moves the same messages (the summary
        shapes never change), so telemetry needs no per-leaf walk inside
        the loop: shares travel as w x R slices of the flat uint32 tile
        buffer (pallas) or uint64 leaf tensors (reference); unprotected
        leaves go plain in f64.

        ``include_count`` mirrors the coordinator wire protocol's extra
        ``count`` leaf; ``num_live_centers`` switches from secure_fit's
        all-w accounting to the coordinator's per-center slicing (each
        online center receives one 1/w slice of the share buffer).
        ``num_configs`` multiplies the whole message set for the
        multiconfig wire's (lambda x fold, or study-slot) config axis —
        every config ships its own summary tree per round — and
        ``extra_scalars`` accounts for the selection path's additional
        held-out-metric leaves (val deviance / correct / count) riding
        in each config's protected buffer.
        """
        extra = (2 if include_count else 1) + extra_scalars
        n_protected = 0
        if protect in ("gradient", "both"):
            n_protected += d
        if protect in ("hessian", "both"):
            n_protected += d * d
        if protect != "none":
            n_protected += extra
        scheme = self.scheme
        w, num_r = scheme.num_shares, scheme.field.num_residues
        share_bytes = 0
        if n_protected:
            if self.backend == "pallas":
                rows = _rows_for(n_protected, ROW_ALIGN)
                share_bytes = w * num_r * rows * LANES * 4  # uint32 wire
            else:
                share_bytes = w * num_r * n_protected * 8  # uint64 leaves
            if num_live_centers is not None:
                share_bytes = (share_bytes // w) * num_live_centers
        n_plain = 0
        if protect in ("none", "hessian"):
            n_plain += d
        if protect in ("none", "gradient"):
            n_plain += d * d
        if protect == "none":
            n_plain += extra
        return num_configs * num_parts * (share_bytes + n_plain * 8)

    # in-SPMD wires -----------------------------------------------------------
    def psum(self, tree, axis_name: str, key: jax.Array,
             dtype=jnp.float32, reveal: str = "replicated",
             points: Sequence[int] | None = None, out: str = "tree"):
        """Secret-shared all-reduce over a mesh axis (the 1D wire).

        See :func:`secure_psum` (the traced module-level entry) for the
        full wire/reveal/out contract; this method is the chain itself.
        """
        if reveal not in REVEAL_MODES:
            raise ValueError(f"reveal must be one of {REVEAL_MODES}")
        if out not in OUT_MODES:
            raise ValueError(f"out must be one of {OUT_MODES}")
        if out == "tile" and reveal != "sharded":
            raise ValueError(
                "out='tile' only makes sense with reveal='sharded' — the "
                "replicated reveal already holds the full aggregate "
                "everywhere"
            )
        pts = self._validated_points(points)
        num_devices = jax.lax.axis_size(axis_name)
        check_aggregation_headroom(num_devices, self.scheme.field)
        if self.overflow_check:
            # every device's contribution is bounded by capacity / D so the
            # D-way field sum cannot overflow (headroom_ok, hard-failure
            # form)
            jax.tree_util.tree_map(
                lambda leaf: self.codec.check_headroom(
                    leaf, num_addends=num_devices, what="secure_psum"
                ),
                tree,
            )
        idx = jax.lax.axis_index(axis_name)
        key = self.round_key(key, idx)
        if self.backend != "pallas":
            if reveal != "replicated":
                raise ValueError(
                    "reveal='sharded' needs the flat-buffer wire (pallas "
                    "backend); the per-leaf reference oracle is "
                    "replicated-only"
                )
            return _secure_psum_per_leaf(tree, axis_name, key, self, pts,
                                         dtype)

        # sharded reveal scatters the rows axis: align rows to lcm(8, D) so
        # every device's tile keeps the (8, 128) sublane layout (the zero
        # tail packs to zero shares — benign through reduce and reveal)
        row_align = ROW_ALIGN if reveal == "replicated" else math.lcm(
            ROW_ALIGN, num_devices
        )
        buf, layout = pack_pytree(tree, row_align=row_align)
        shares = _protect_flat(
            key, buf, self.scheme, self.codec.frac_bits, layout.rows,
            points=pts,
        )  # (t', R, rows, 128) uint32 — only the reveal subset exists
        if reveal == "replicated":
            summed = self.allreduce(shares, axis_name)
            flat = _reveal_flat(summed, self.scheme, self.codec.frac_bits,
                                pts)
            return unpack_pytree(flat, layout, dtype=dtype)
        tile = self.allreduce(
            shares, axis_name, scatter_axis=2
        )  # (t', R, rows / D, 128): this device's slice of the residues
        flat_tile = _reveal_flat(
            tile, self.scheme, self.codec.frac_bits, pts
        ).astype(dtype)  # decode locally, gather plaintext (dtype-sized)
        if out == "tile":
            return ShardedAggregate(flat_tile, layout, num_devices)
        flat = jax.lax.all_gather(flat_tile, axis_name, axis=0, tiled=True)
        return unpack_pytree(flat, layout, dtype=dtype)

    def psum_2d(self, tree, key: jax.Array, dtype=jnp.float32,
                pod_axis: str = POD_AXIS, share_axis: str = SHARE_AXIS,
                points: Sequence[int] | None = None):
        """Secret-shared all-reduce on a 2D (pod, share) mesh.

        Call from inside ``shard_map`` over
        :func:`repro.distributed.multihost.pod_share_mesh`.  The
        share-axis size must equal the reveal subset (default: the
        scheme threshold t).  Every (pod, share) device derives the SAME
        sharing polynomial for its pod (the rng folds only the pod
        index), keeps only its own slice, and the two collectives are

        1. uint64 psum over ``pod_axis``  — Algorithm 2 at center j;
        2. weighted uint64 psum over ``share_axis`` — the distributed
           Lagrange reveal (:func:`_distributed_reveal`).

        Bit-equal to the 1D :meth:`psum` wire: both reveal the exact
        field encoding of the global sum.
        """
        if self.backend != "pallas":
            raise ValueError("secure_psum_2d needs the flat-buffer wire "
                             "(pallas backend)")
        pts = self._validated_points(points)
        k = jax.lax.axis_size(share_axis)
        if k != len(pts):
            raise ValueError(
                f"share axis has {k} devices but the reveal subset is "
                f"{len(pts)} points — one center per revealed slice"
            )
        num_pods = jax.lax.axis_size(pod_axis)
        check_aggregation_headroom(num_pods, self.scheme.field)
        key = self.round_key(key, jax.lax.axis_index(pod_axis))
        buf, layout = pack_pytree(tree)
        shares = _protect_flat(
            key, buf, self.scheme, self.codec.frac_bits, layout.rows,
            points=pts,
        )  # (k, R, rows, 128); same on every share column of this pod
        j = jax.lax.axis_index(share_axis)
        mine = jnp.take(shares, j, axis=0)  # (R, rows, 128): center j's
        agg_slice = self.allreduce(mine, pod_axis, residue_axis=0)
        flat = _distributed_reveal(
            agg_slice, self.scheme, self.codec, pts, share_axis,
            jnp.float64,
        )
        return unpack_pytree(flat, layout, dtype=dtype)


def _secure_psum_per_leaf(tree, axis_name: str, key: jax.Array,
                          agg: SecureCollective, points: tuple[int, ...],
                          dtype):
    """The original per-leaf uint64 wire: the bit-exactness oracle.

    Protects leaf by leaf through the reference pipeline and all-reduces
    every holder's full (w, R, ...) uint64 share tree — w * R * 8 bytes
    per parameter on the wire, reconstruction on every device.  Kept (and
    parametrized in tests) as the oracle the flat-buffer wire is measured
    against; new code wants the flat path.
    """
    protected = agg.protect(key, tree)
    aggregated = jax.tree_util.tree_map(
        lambda s: _field_allreduce(s, axis_name, agg.scheme.field), protected
    )
    sel = jnp.asarray([p - 1 for p in points])
    subset = jax.tree_util.tree_map(lambda s: s[sel], aggregated)
    return agg.reveal(subset, points=points, dtype=dtype)


@_traced("secure_psum")
def secure_psum(tree, axis_name: str, key: jax.Array,
                aggregator: SecureCollective | None = None,
                dtype=jnp.float32, reveal: str = "replicated",
                points: Sequence[int] | None = None,
                out: str = "tree"):
    """Secret-shared all-reduce over a mesh axis (SPMD Algorithm 1, 11-13).

    Per device: pack the local float tree into ONE flat (rows, 128) tile
    buffer, push it through the fused fixed-point-encode + Horner-share
    kernel (fresh randomness per device via axis-index key folding), and
    reduce the uint32 share buffer over ``axis_name`` — which IS Algorithm
    2 executed by the virtual Computation Centers — then reveal + decode
    only the global sum via the fused Lagrange+CRT kernel.  Only the
    ``points`` subset of share slices (default: the first t, the unified
    reveal default) is ever evaluated or transmitted, so the wire carries
    a (t, R, rows, 128) uint32 buffer — t/w of the slices at half the
    element width of the per-leaf uint64 tree.

    ``reveal`` selects where the residues live between reduction and
    decode:

    * ``"replicated"`` — one `psum`; every device holds the full summed
      share buffer and reconstructs its own copy of the aggregate
      (programming-model convenience, the pre-sharded behavior).
    * ``"sharded"`` — `psum_scatter` over the rows axis: each device only
      ever holds a 1/D row-tile of the aggregated residues, reveals just
      that tile, and a final all-gather assembles the *decoded* float
      aggregate — the share buffer crosses the wire once instead of
      twice, cutting the all-reduce payload roughly in half (the gathered
      plaintext is ``dtype``-sized, far smaller than the share buffer).

    ``out`` selects the return shape of the sharded reveal:

    * ``"tree"`` (default) — all-gather the decoded tiles and unpack the
      full float pytree on every device (the historical behavior).
    * ``"tile"`` — skip the gather: return a :class:`ShardedAggregate`
      whose ``tile`` leaf is this device's decoded plaintext row-tile.
      ``.gather(axis_name)`` reproduces ``out="tree"`` bit-exactly;
      shard-wise consumers never pay for the assembled tree.

    Passing ``aggregator=SecureCollective(backend="reference")`` selects
    the original per-leaf uint64 wire (replicated reveal only) — the
    bit-exactness oracle.  Cryptographically, both modes only ever
    *combine* shares (never reveal an individual contribution) before the
    aggregate reconstruction, matching the paper's trust model where
    centers jointly reveal aggregates.
    """
    agg = aggregator or SecureCollective(backend="pallas")
    return agg.psum(tree, axis_name, key, dtype=dtype, reveal=reveal,
                    points=points, out=out)


def secure_psum_2d(tree, key, aggregator: SecureCollective | None = None,
                   dtype=jnp.float32, pod_axis: str = POD_AXIS,
                   share_axis: str = SHARE_AXIS,
                   points: Sequence[int] | None = None):
    """Module-level entry for the 2D (pod, share) wire; see :meth:`psum_2d`.

    Re-exported by :mod:`repro.distributed.multihost` (the historical
    home); the chain itself lives on :class:`SecureCollective`.
    """
    agg = aggregator or SecureCollective(backend="pallas")
    return agg.psum_2d(tree, key, dtype=dtype, pod_axis=pod_axis,
                       share_axis=share_axis, points=points)
