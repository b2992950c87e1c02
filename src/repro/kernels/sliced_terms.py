"""Float64 gradient and deviance terms from bf16 slices of X, on the MXU.

A TPU has no float64 unit, so XLA emulates a float64 contraction over X,
and it rebuilds its own split of X for every call.  This module splits X
once (``cut_slices``) and computes the terms every round from the cut
slices (``sliced_terms``) as bf16 x bf16 -> f32 dots whose every partial
is an integer the f32 accumulator holds exactly: the scheme of Ozaki,
Ogita, Oishi and Rump (2012).

Digits.  A set of values is scaled by one power of two ``2**(e + 1)``
with every ``|v| / 2**(e + 1) < 1/2`` and cut into signed base-256
digits, ``v = 2**(e + 1) * sum_i a_i * 256**-(i + 1)``, each digit the
nearest integer to what is left, so ``|a_i| <= 128``, exact in bf16.
X is cut once into ``K = 8`` digits with one exponent per (site, row):
the tail left is at most ``2**-63`` of the row's largest entry.  beta
is cut each round with one exponent, and the row-scaled residual with
one per (site, slab of ``SLAB`` rows) into ``KQ = K + 3`` digits, so a
row up to 2**24 smaller than its slab's largest keeps its precision.

Exactness.  Pairs of digits are grouped by level ``l = i + j``: levels
``l < K`` for z, ``l < KQ`` for g.  A level holds at most ``K`` pairs,
and a pair sums 128 products of at most ``2**14`` (``d <= 128`` entries
of a row for z, ``SLAB`` rows for g), so every partial of a level is an
integer of at most ``2**24``: f32 accumulates it exactly in any order.
The levels are scaled and combined in float64 on the small outputs,
(K, S, N) and (S, slabs, KQ, d).  The error of z is about ``d * 2**-53
* max|x| * max|beta|`` per row at most, a float64 dot's own bound when a
row's entries are alike in size.

Layout.  The slices are one bf16 array (S, Np, K * d), Np = N rounded
up to a slab: z contracts its minor axis, g its rows slab by slab, and
neither reads it in another layout.  They cost ``2 K / 8 = 2`` times the
bytes of a float64 X.  Past ``MAX_DIM`` features a row's products could
pass 2**24, so wider X keeps the float64 contractions.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["K", "KQ", "SLAB", "MAX_DIM", "XSlices", "cut_slices",
           "z_levels", "g_levels", "sliced_terms"]

K = 8  # digits of X: 64 bits, past float64's 53
SLAB = 128  # rows per exponent of the scaled residual
MAX_DIM = 128  # features a row of digits may hold and stay exact
KQ = K + 3  # digits of the residual: 24 more bits for rows of unlike size
_BASE = 256.0


class XSlices(NamedTuple):
    """X as base-256 digits: ``X[s, n] = scale[s, n] * sum_i digits[s, n,
    i*d:(i+1)*d] * 256**-(i + 1)`` up to the tail."""

    digits: jnp.ndarray  # (S, Np, K * d) bfloat16 integers, |a| <= 128
    scale: jnp.ndarray  # (S, Np) float64 powers of two, 2**(e + 1)


def _pow2(e):
    """2**e as float64, built from f32 exponent bits: exact for e in
    [-126, 127], clipped there."""
    bits = jnp.left_shift(jnp.clip(e, -126, 127) + 127, 23)
    return jax.lax.bitcast_convert_type(bits.astype(jnp.int32),
                                        jnp.float32).astype(jnp.float64)


def _exponent(m):
    """e with m < 2**e for m >= 0 (m = 0 gives 0), from m's f32 image:
    rounding up to a power of two only leaves one bit unused."""
    return jnp.frexp(m.astype(jnp.float32))[1]


def _digits(v, inv_scale, k=K, axis=0):
    """The k digits of v * inv_scale (|.| < 1/2), stacked on ``axis``."""
    r = v * inv_scale
    out = []
    for _ in range(k):
        t = r * _BASE
        a = jnp.round(t.astype(jnp.float32)).astype(jnp.float64)
        r = t - a
        # t's f32 image may round onto the half-integer t sits beside
        step = jnp.where(r > 0.5, 1.0, jnp.where(r < -0.5, -1.0, 0.0))
        out.append((a + step).astype(jnp.bfloat16))
        r = r - step
    return jnp.stack(out, axis=axis)


@jax.jit
def cut_slices(X) -> XSlices:
    """Cut the packed float64 payload (S, N, d) into its digit slices."""
    s_dim, n, d = X.shape
    if d > MAX_DIM:
        raise ValueError(f"the sliced terms hold exact for d <= {MAX_DIM}")
    n_pad = -(-n // SLAB) * SLAB
    X = jnp.pad(X.astype(jnp.float64), ((0, 0), (0, n_pad - n), (0, 0)))
    e = _exponent(jnp.max(jnp.abs(X), axis=2))
    a = _digits(X, _pow2(-e - 1)[..., None], axis=2)  # (S, Np, K, d)
    return XSlices(a.reshape(s_dim, n_pad, K * d), _pow2(e + 1))


def _unfused(levels):
    """The f32 levels, written out before any float64 work reads them.

    XLA:TPU emulates float64 as pairs of f32.  From 64 sites of 3,760
    rows on it fused that emulation of the level sum into the z dot's
    output, and on a TPU v5e the fused program returned wrong z (right
    at 4 and 16 sites).  The barrier keeps the two apart.
    """
    return jax.lax.optimization_barrier(levels)


def _level_weights(levels):
    """256**-(l + 2), the weight of level l of a pair of digit sets."""
    return jnp.asarray(np.ldexp(1.0, -8 * (np.arange(levels) + 2)))


def z_levels(beta, digits):
    """The K levels of z = X beta, f32 (K, S, Np), in units of the row's
    scale times the returned scale of beta."""
    kd = digits.shape[2]
    beta = beta.astype(jnp.float64)
    eb = _exponent(jnp.max(jnp.abs(beta)))
    b = _digits(beta, _pow2(-eb - 1))  # (K, d)
    # level l takes digit l - i of beta against slice i of X
    lvl = jnp.arange(K)[:, None] - jnp.arange(K)[None, :]
    bmat = jnp.where((lvl >= 0)[..., None], b[jnp.clip(lvl, 0, K - 1)],
                     jnp.zeros((), jnp.bfloat16)).reshape(K, kd)
    zl = jax.lax.dot_general(
        bmat, digits, (((1,), (2,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return _unfused(zl), _pow2(eb + 1)


def g_levels(q, digits):
    """The KQ levels of g = X^T r per slab, f32 (S, slabs, KQ, d), for
    the row-scaled residual ``q`` (S, Np), and each slab's scale (S,
    slabs)."""
    s_dim, n_pad, kd = digits.shape
    slabs = n_pad // SLAB
    q = q.reshape(s_dim, slabs, SLAB)
    eq = _exponent(jnp.max(jnp.abs(q), axis=2))
    c = _digits(q, _pow2(-eq - 1)[..., None], KQ, axis=2)
    d = kd // K
    pairs = jax.lax.dot_general(
        c, digits.reshape(s_dim, slabs, SLAB, kd),
        (((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32,
    )  # (S, slabs, j, i * d + k)
    # level l sums the pairs i + j = l: at most K * 2**21, exact in f32
    gl = jnp.stack([
        sum(pairs[:, :, lv - i, i * d:(i + 1) * d]
            for i in range(max(0, lv - KQ + 1), min(lv, K - 1) + 1))
        for lv in range(KQ)
    ], axis=2)
    return _unfused(gl), _pow2(eq + 1)


def sliced_terms(beta, slices: XSlices, y, counts):
    """The f64 z/g/dev terms and the f32 IRLS weights, as ``_sim_terms``
    gives them, from the cut slices of X."""
    with jax.named_scope("f64_terms"):
        digits, xscale = slices
        n_pad, n = digits.shape[1], y.shape[1]
        zl, bscale = z_levels(beta, digits)
        zfix = jnp.sum(_level_weights(K)[:, None, None]
                       * zl.astype(jnp.float64), axis=0)
        z = (zfix * xscale * bscale)[:, :n]
        mask = (jnp.arange(n, dtype=jnp.int32)[None, :]
                < counts[:, None]).astype(jnp.float64)
        p = jax.nn.sigmoid(z)
        resid = (y - p) * mask
        gl, qscale = g_levels(
            jnp.pad(resid, ((0, 0), (0, n_pad - n))) * xscale, digits)
        gfix = jnp.sum(_level_weights(KQ)[:, None]
                       * gl.astype(jnp.float64), axis=2)  # (S, slabs, d)
        g = jnp.sum(qscale[..., None] * gfix, axis=1)
        w32 = ((p * (1.0 - p)) * mask).astype(jnp.float32)
        dev = -2.0 * jnp.sum((y * z - jnp.logaddexp(0.0, z)) * mask, axis=1)
        return w32, g, dev
