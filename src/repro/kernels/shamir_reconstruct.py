"""Pallas TPU kernel: Lagrange reconstruction + CRT decode for Shamir shares.

The mirror of ``shamir_poly_pallas``: reconstruction at x = 0 is a public
linear combination sum_i L_i(0) * share_i (mod p) — k fused modular
multiply-adds per element, fully data-parallel.  The Lagrange weights
L_i(0) depend only on the (public) evaluation points, so they are computed
host-side with Python big-ints and baked into the kernel as static uint32
constants; no in-graph modular inverses.

Field elements use the same 16-bit-limb ``mulmod31`` representation as
share generation (the VPU has no 64-bit multiply).  Both residues of the
CRT pair are processed in ONE kernel launch: the block carries a leading
residue axis and each residue's weights/modulus are unrolled statically.

With ``garner=True`` the kernel additionally fuses the first (and only
modular) step of CRT recombination — Garner's mixed-radix digit

    k = (r2 - r1) * p1^{-1}  (mod p2)

— which is pure 31-bit field math and therefore VPU-native.  The caller
finishes with ``x = r1 + p1 * k`` in uint64 outside the kernel (three
elementwise ops); everything superlinear stays in the kernel.

Grid: shares reshaped to (R, k, rows, 128) tiles by ops.py; one program per
(block_rows, 128) tile reconstructs all residues for its tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .backend import ZERO, resolve_interpret
from .shamir_poly import addmod, mulmod31

__all__ = ["shamir_reconstruct_pallas", "lagrange_weights_host"]

DEFAULT_BLOCK_ROWS = 256


def lagrange_weights_host(
    points, moduli
) -> tuple[tuple[int, ...], ...]:
    """L_i(0) per residue as nested Python-int tuples (static kernel args).

    ``points`` are the public 1-based share evaluation points; weights are
    computed with big-int modular inverses host-side (leaks nothing).
    """
    if len(set(points)) != len(points):
        raise ValueError(
            f"reconstruction points must be distinct, got {tuple(points)}"
        )
    out = []
    for p in moduli:
        row = []
        for i, xi in enumerate(points):
            num, den = 1, 1
            for j, xj in enumerate(points):
                if i == j:
                    continue
                num = (num * xj) % p
                den = (den * ((xj - xi) % p)) % p
            row.append((num * pow(den, p - 2, p)) % p)
        out.append(tuple(row))
    return tuple(out)


def _kernel(shares_ref, out_ref, *, lams, moduli, garner):
    num_residues = len(moduli)
    recs = []
    for r in range(num_residues):
        p = moduli[r]
        acc = mulmod31(shares_ref[r, 0], np.uint32(lams[r][0]), p)
        for i in range(1, len(lams[r])):
            term = mulmod31(shares_ref[r, i], np.uint32(lams[r][i]), p)
            acc = addmod(acc, term, p)
        recs.append(acc)
    if garner:
        # Garner digit for the CRT pair (p1 > p2): k = (r2 - r1)/p1 mod p2.
        p1, p2 = moduli
        assert p1 > p2, "garner layout assumes moduli sorted descending"
        inv_p1 = np.uint32(pow(p1 % p2, p2 - 2, p2))
        pp2 = np.uint32(p2)
        r1, r2 = recs
        r1m = jnp.where(r1 >= pp2, r1 - pp2, r1)  # r1 < p1 = p2 + (c2 - c1)
        diff = jnp.where(r2 >= r1m, r2 - r1m, r2 + (pp2 - r1m))
        out_ref[0, ...] = r1
        out_ref[1, ...] = mulmod31(diff, inv_p1, p2)
    else:
        for r in range(num_residues):
            out_ref[r, ...] = recs[r]


@functools.partial(
    jax.jit,
    static_argnames=("lams", "moduli", "garner", "block_rows", "interpret"),
)
def shamir_reconstruct_pallas(
    shares: jnp.ndarray,  # (R, k, rows, 128) uint32, reduced per residue
    lams: tuple[tuple[int, ...], ...],  # static public Lagrange weights
    moduli: tuple[int, ...],
    garner: bool = False,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Returns (R, rows, 128) uint32: reconstructed residues, or with
    ``garner=True`` (R == 2 only) the pair (r1, garner digit k)."""
    num_residues, k, rows, lanes = shares.shape
    assert lanes == 128 and rows % block_rows == 0, "ops.py reshapes/pads"
    assert len(moduli) == num_residues and len(lams) == num_residues
    assert all(len(l) == k for l in lams)
    if garner and num_residues != 2:
        raise ValueError("garner fusion needs exactly 2 residues")
    grid = (rows // block_rows,)
    kernel = functools.partial(
        _kernel, lams=lams, moduli=moduli, garner=garner
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (num_residues, k, block_rows, 128),
                lambda i: (ZERO, ZERO, i, ZERO),
            ),
        ],
        out_specs=pl.BlockSpec(
            (num_residues, block_rows, 128), lambda i: (ZERO, i, ZERO)
        ),
        out_shape=jax.ShapeDtypeStruct(
            (num_residues, rows, 128), jnp.uint32
        ),
        interpret=resolve_interpret(interpret),
    )(shares)
