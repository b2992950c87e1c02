"""Pallas TPU kernel: Shamir share generation in 32-bit limbs.

Share generation evaluates one random degree-(t-1) polynomial per secret
element at x = 1..w — t-1 fused modular multiply-adds per element, fully
data-parallel.  The TPU adaptation is the interesting part: the VPU has no
64-bit integer multiply, so the uint64 reference math does not port.  We
represent reduced field elements (< 2**31) in uint32 and implement

    mulmod(a, b) mod p,  p = 2**31 - c  (pseudo-Mersenne; c = 1 or 19)

with 16-bit limb decomposition: a = a0 + a1*2**16, b = b0 + b1*2**16, all
four partial products < 2**32 fit uint32, and each partial is folded with
x mod p = (x & (2**31-1)) + c * (x >> 31)  (one conditional subtract after).
Multiplication by the Horner point x <= w (small public constant) only needs
the b1 < 2**15 case, keeping every intermediate in range.  This replaces the
big-int field arithmetic a CPU implementation would use — same field, same
security, MXU/VPU-native word sizes.

Grid: secrets reshaped to (R, rows, 128) lanes by ops.py; one program per
(block_rows, 128) tile computes every residue's shares for its tile (w is
small and static).  Working set: R * (t-1 + 1 + P) * block_rows * 128
uint32 words.  The float -> residue lift of the fused protect path runs in
XLA ahead of the kernel (``encode_residues``): it needs 64-bit integers,
which the kernel never sees.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .backend import ZERO, resolve_interpret

__all__ = [
    "shamir_poly_pallas",
    "shamir_encode_share_pallas",
    "encode_residues",
    "mulmod31",
    "addmod",
]

DEFAULT_BLOCK_ROWS = 256
MASK31 = np.uint32(2**31 - 1)  # numpy scalar: safe inside pallas kernels


def addmod(a: jnp.ndarray, b: jnp.ndarray, p: int) -> jnp.ndarray:
    """(a + b) mod p for reduced uint32 inputs (sum < 2**32)."""
    s = a + b
    pp = np.uint32(p)
    return jnp.where(s >= pp, s - pp, s)


def _fold(x: jnp.ndarray, p: int, c: int) -> jnp.ndarray:
    """x mod p for x < 2**32, p = 2**31 - c: fold high bit with weight c."""
    r = (x & MASK31) + np.uint32(c) * (x >> np.uint32(31))
    pp = np.uint32(p)
    r = jnp.where(r >= pp, r - pp, r)  # r < 2**31 + 19*1 after one fold
    return jnp.where(r >= pp, r - pp, r)


def mulmod31(a: jnp.ndarray, b: jnp.ndarray, p: int) -> jnp.ndarray:
    """(a * b) mod p via 16-bit limbs, p = 2**31 - c, a,b reduced < p.

    a0b0 < 2**32, cross terms < 2**31 each; shifts are folded with the
    pseudo-Mersenne identity 2**31 === c (mod p):
      2**16 * x mod p and 2**32 * x mod p = c * (2 * x) ... handled by
      iterated folding of (x << 16).
    """
    c = 2**31 - p
    a0 = a & np.uint32(0xFFFF)
    a1 = a >> np.uint32(16)  # < 2**15
    b0 = b & np.uint32(0xFFFF)
    b1 = b >> np.uint32(16)  # < 2**15

    def shl16_mod(x):
        # (x * 2**16) mod p for reduced x < p: split off top 15 bits
        hi = x >> np.uint32(15)  # < 2**16
        lo = x & np.uint32(0x7FFF)  # < 2**15
        # x*2**16 = hi*2**31 + lo*2**16  ===  hi*c + lo*2**16 (mod p)
        return _fold((lo << np.uint32(16)) + np.uint32(c) * hi, p, c)

    t00 = _fold(a0 * b0, p, c)  # < 2**32 -> reduced
    t01 = _fold(a0 * b1, p, c)
    t10 = _fold(a1 * b0, p, c)
    t11 = _fold(a1 * b1, p, c)
    mid = shl16_mod(addmod(t01, t10, p))
    hi = shl16_mod(shl16_mod(t11))
    return addmod(addmod(t00, mid, p), hi, p)


def _share_kernel(secret_ref, coeffs_ref, out_ref, *, points, moduli):
    """Horner evaluation of every residue's polynomial at every point.

    secret (R, br, 128), coeffs (R, t-1, br, 128) -> out (R, P, br, 128),
    all uint32 reduced field elements: no 64-bit type enters the kernel.
    """
    t_minus_1 = coeffs_ref.shape[1]
    for r, p in enumerate(moduli):
        secret = secret_ref[r]
        for out_idx, j in enumerate(points):
            xj = np.uint32(j)
            acc = jnp.zeros_like(secret)
            for k in range(t_minus_1 - 1, -1, -1):
                acc = addmod(mulmod31(acc, xj, p), coeffs_ref[r, k], p)
            out_ref[r, out_idx, ...] = addmod(
                mulmod31(acc, xj, p), secret, p
            )


def _share_residues(secret, coeffs, points, moduli, block_rows, interpret,
                    name):
    """(R, rows, 128) residues + (R, t-1, rows, 128) coefficients ->
    (R, len(points), rows, 128) uint32 shares in one launch, under the
    kernel name ``name`` (what a device trace shows for the launch)."""
    num_residues, rows, lanes = secret.shape
    assert lanes == 128 and rows % block_rows == 0, "ops.py reshapes/pads"
    assert len(moduli) == num_residues == coeffs.shape[0]
    t_minus_1 = coeffs.shape[1]
    kernel = functools.partial(_share_kernel, points=points, moduli=moduli)
    return pl.pallas_call(
        kernel,
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((num_residues, block_rows, 128),
                         lambda i: (ZERO, i, ZERO)),
            pl.BlockSpec((num_residues, t_minus_1, block_rows, 128),
                         lambda i: (ZERO, ZERO, i, ZERO)),
        ],
        out_specs=pl.BlockSpec(
            (num_residues, len(points), block_rows, 128),
            lambda i: (ZERO, ZERO, i, ZERO),
        ),
        out_shape=jax.ShapeDtypeStruct(
            (num_residues, len(points), rows, 128), jnp.uint32
        ),
        interpret=resolve_interpret(interpret),
        name=name,
    )(secret, coeffs)


@functools.partial(
    jax.jit,
    static_argnames=("num_shares", "modulus", "block_rows", "interpret"),
)
def shamir_poly_pallas(
    secret: jnp.ndarray,  # (rows, 128) uint32, reduced mod modulus
    coeffs: jnp.ndarray,  # (t-1, rows, 128) uint32, reduced
    num_shares: int,
    modulus: int,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Returns (num_shares, rows, 128) uint32 shares (one residue)."""
    out = _share_residues(
        secret[None], coeffs[None], tuple(range(1, num_shares + 1)),
        (modulus,), block_rows, interpret, "shamir_poly_pallas",
    )
    return out[0]


def encode_residues(x: jnp.ndarray, moduli: tuple[int, ...],
                    frac_bits: int) -> jnp.ndarray:
    """Fixed-point encode of a float payload to (R, ...) uint32 residues.

    The same map as ``FixedPointCodec.encode`` (round(x * 2**frac_bits)
    in float64, clipped to +-max_signed, lifted mod each p_r), so the
    fused path stays bit-identical to the reference codec.  It runs as
    XLA ops ahead of the share kernel: the exact lift needs 64-bit
    arithmetic, and Mosaic has no 64-bit types.
    """
    max_signed = (math.prod(moduli) - 1) // 2
    lim = float(max_signed)
    s = jnp.clip(
        jnp.round(x.astype(jnp.float64) * float(1 << frac_bits)), -lim, lim
    ).astype(jnp.int64)
    return jnp.stack([(s % np.int64(p)).astype(jnp.uint32) for p in moduli])


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_shares", "moduli", "frac_bits", "block_rows", "interpret",
        "points",
    ),
)
def shamir_encode_share_pallas(
    x: jnp.ndarray,  # (rows, 128) float32/float64 payload
    coeffs: jnp.ndarray,  # (R, t-1, rows, 128) uint32, reduced per residue
    num_shares: int,
    moduli: tuple[int, ...],
    frac_bits: int,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
    points: tuple[int, ...] | None = None,
) -> jnp.ndarray:
    """Fixed-point encode + Horner share evaluation of every residue.
    Returns (R, len(points), rows, 128) uint32.

    ``points`` (default 1..num_shares) are the public evaluation points to
    emit, statically unrolled like the full-fan-out loop — the sharded
    ``secure_psum`` wire only ever transmits a threshold subset of slices,
    so it evaluates only those, skipping (w - t)/w of the Horner work.
    Slice j of the output is the share at ``points[j]`` on every path.

    Equivalent to ``FixedPointCodec.encode`` followed by the share kernel:
    ``encode_residues`` lifts the payload in XLA (float64 exact to the
    codec's full 61-bit range), and one kernel launch evaluates all
    residues' polynomials on uint32 words.
    """
    rows, lanes = x.shape
    assert lanes == 128 and rows % block_rows == 0, "ops.py reshapes/pads"
    if points is None:
        points = tuple(range(1, num_shares + 1))
    assert all(1 <= p <= num_shares for p in points)
    return _share_residues(
        encode_residues(x, moduli, frac_bits), coeffs, tuple(points),
        tuple(moduli), block_rows, interpret, "shamir_encode_share_pallas",
    )
