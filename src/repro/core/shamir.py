"""Shamir t-of-w secret sharing, vectorized over arbitrary tensors/pytrees.

Implements the paper's protection mechanism (Eq. 7): each secret ``m`` is
embedded as the constant term of a random degree-(t-1) polynomial
``q(x) = m + a_1 x + ... + a_{t-1} x^{t-1}`` over a prime field; share ``j``
is ``(j, q(j))`` for j = 1..w.  Reconstruction is Lagrange interpolation at 0
using any t shares.  Everything is elementwise over tensors: one independent
polynomial per tensor element, evaluated with Horner's rule.

Share tensors have shape ``(w, R, *secret_shape)`` where R is the field's
residue count.  The leading axis is the *holder* (Computation Center) axis —
in deployment each slice lives at a different center; in our SPMD simulation
it is carried as a leading dim (or sharded over a mesh axis by the caller).

Backends
--------
``backend="reference"`` (default) runs the uint64 ``%``-reduction math in
plain jnp — the correctness oracle, one dispatch per field op.
``backend="pallas"`` routes the same Horner/Lagrange loops through the
TPU kernels (``kernels/shamir_poly.py`` / ``kernels/shamir_reconstruct.py``,
16-bit-limb ``mulmod31``; compiled on a TPU, interpreted on the CPU —
``kernels.backend`` decides).  Given identical
coefficients the two backends produce **bit-identical** shares and
reconstructions — both compute exact field elements; only the word-size
decomposition differs (``share_with_coeffs`` exposes the deterministic
entry point for that contract).
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp

from .field import (
    FieldSpec,
    FIELD_WIDE,
    fadd,
    fmul,
    random_elements,
)

__all__ = ["ShamirScheme", "lagrange_coeffs_at_zero", "BACKENDS"]

BACKENDS = ("reference", "pallas")


def lagrange_coeffs_at_zero(
    points: Sequence[int], field: FieldSpec
) -> jnp.ndarray:
    """Public Lagrange weights L_i(0) for reconstruction, per residue.

    Returns (R, len(points)) uint64.  Computed host-side with Python ints —
    the points are public (they identify Computation Centers), so this leaks
    nothing and avoids in-graph modular inverses.
    """
    from ..kernels.shamir_reconstruct import lagrange_weights_host

    return jnp.asarray(
        lagrange_weights_host(tuple(points), field.moduli), dtype=jnp.uint64
    )


@dataclasses.dataclass(frozen=True)
class ShamirScheme:
    """t-of-w threshold scheme over ``field`` with a kernel backend switch."""

    threshold: int = 2  # t: min cooperating centers to reconstruct
    num_shares: int = 3  # w: total Computation Centers
    field: FieldSpec = FIELD_WIDE
    backend: str = "reference"  # "reference" (jnp oracle) | "pallas"

    def __post_init__(self):
        if not (1 <= self.threshold <= self.num_shares):
            raise ValueError("need 1 <= t <= w")
        if self.num_shares >= min(self.field.moduli):
            raise ValueError("w must be < field modulus")
        if self.backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}")

    # -- sharing ------------------------------------------------------------
    def share(self, key: jax.Array, secret: jnp.ndarray) -> jnp.ndarray:
        """Split field elements (R, ...) into shares (w, R, ...).

        Coefficients are fresh uniform field elements per tensor element
        (information-theoretic hiding below threshold t); evaluation is
        delegated to ``share_with_coeffs``.
        """
        coeffs = random_elements(
            key, (self.threshold - 1,) + secret.shape[1:], self.field
        )  # (R, t-1, ...)
        return self.share_with_coeffs(secret, coeffs)

    def share_with_coeffs(
        self, secret: jnp.ndarray, coeffs: jnp.ndarray
    ) -> jnp.ndarray:
        """Deterministic share evaluation given coefficients (R, t-1, ...).

        Both backends produce bit-identical output for the same inputs —
        this is the backend-equivalence contract the tests pin down.
        """
        t, w = self.threshold, self.num_shares
        if coeffs.shape[:2] != (self.field.num_residues, t - 1):
            raise ValueError(
                f"coeffs must be (R, t-1, ...), got {coeffs.shape}"
            )
        if t == 1:  # q(x) = m: every share is the secret itself
            return jnp.broadcast_to(secret, (w,) + secret.shape)
        if self.backend == "pallas":
            return self._share_pallas(secret, coeffs)
        return self._share_reference(secret, coeffs)

    def _share_reference(self, secret, coeffs):
        t, w, field = self.threshold, self.num_shares, self.field
        coeffs = jnp.swapaxes(coeffs, 0, 1)  # (t-1, R, ...)

        def eval_at(x: int) -> jnp.ndarray:
            # q(x) = (..(a_{t-1} x + a_{t-2}) x + ..) x + m, per residue
            acc = jnp.zeros_like(secret)
            xs = jnp.full((), x, dtype=jnp.uint64)
            for k in range(t - 2, -1, -1):
                acc = fadd(fmul(acc, xs, field), coeffs[k], field)
            return fadd(fmul(acc, xs, field), secret, field)

        return jnp.stack([eval_at(j) for j in range(1, w + 1)], axis=0)

    def _share_pallas(self, secret, coeffs):
        from ..kernels import ops

        t, w, field = self.threshold, self.num_shares, self.field
        shape = secret.shape[1:]
        per_residue = []
        for r, p in enumerate(field.moduli):
            out = ops.shamir_shares(
                secret[r].reshape(-1).astype(jnp.uint32),
                coeffs[r].reshape(t - 1, -1).astype(jnp.uint32),
                w, p,
            )  # (w, n) uint32
            per_residue.append(
                out.astype(jnp.uint64).reshape((w,) + shape)
            )
        return jnp.stack(per_residue, axis=1)  # (w, R, ...)

    # -- reconstruction -----------------------------------------------------
    def reconstruct(
        self,
        shares: jnp.ndarray,
        points: Sequence[int] | None = None,
    ) -> jnp.ndarray:
        """Recover secret (R, ...) from >= t shares (k, R, ...).

        ``points`` are the 1-based holder ids of the provided share slices
        (default: 1..k).  Any t-subset suffices; extra shares are consistent.
        """
        k = shares.shape[0]
        if points is None:
            points = list(range(1, k + 1))
        if len(points) != k:
            raise ValueError("points must match share count")
        if k < self.threshold:
            raise ValueError(
                f"need >= t={self.threshold} shares, got {k} "
                "(information-theoretically irrecoverable below threshold)"
            )
        if self.backend == "pallas":
            return self._reconstruct_pallas(shares, points)
        return self._reconstruct_reference(shares, points)

    def _reconstruct_reference(self, shares, points):
        lam = lagrange_coeffs_at_zero(points, self.field)  # (R, k)
        field = self.field
        k = shares.shape[0]
        acc = jnp.zeros_like(shares[0])
        for i in range(k):
            li = lam[:, i].reshape(
                (field.num_residues,) + (1,) * (shares.ndim - 2)
            )
            acc = fadd(acc, fmul(shares[i], li, field), field)
        return acc

    def _reconstruct_pallas(self, shares, points):
        from ..kernels import ops

        field = self.field
        shape = shares.shape[2:]
        k = shares.shape[0]
        per_residue = []
        for r, p in enumerate(field.moduli):
            rec = ops.shamir_reconstruct(
                shares[:, r].reshape(k, -1).astype(jnp.uint32),
                tuple(points), p,
            )  # (n,) uint32
            per_residue.append(rec.astype(jnp.uint64).reshape(shape))
        return jnp.stack(per_residue, axis=0)  # (R, ...)

    # -- pytree convenience ---------------------------------------------------
    def share_pytree(self, key: jax.Array, tree):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        keys = jax.random.split(key, len(leaves))
        shared = [self.share(k, leaf) for k, leaf in zip(keys, leaves)]
        return jax.tree_util.tree_unflatten(treedef, shared)

    def reconstruct_pytree(self, tree, points: Sequence[int] | None = None):
        return jax.tree_util.tree_map(
            lambda s: self.reconstruct(s, points), tree
        )
