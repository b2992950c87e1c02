"""Jit'd public wrappers around the Pallas kernels (padding, reshaping).

These are what the rest of the framework calls; each has the same signature
semantics as its pure-jnp oracle in ref.py.  No wrapper takes an
``interpret`` switch: ``backend.interpret_kernels`` decides once from JAX's
backend — Mosaic-compiled kernels on a TPU, the Pallas interpreter on the
CPU, an error anywhere else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .backend import interpret_kernels
from .fused_irls import (
    _cv_sim_terms,
    _sim_terms,
    fused_irls_cv_pallas,
    fused_irls_cv_sim,
    fused_irls_pallas,
    fused_irls_sim,
    gram_hessian_pallas,
)
from .shamir_poly import shamir_encode_share_pallas, shamir_poly_pallas
from .shamir_reconstruct import (
    lagrange_weights_host,
    shamir_reconstruct_pallas,
)

__all__ = ["gram_hessian", "fused_irls", "fused_irls_cv", "shamir_shares",
           "shamir_reconstruct", "shamir_protect_flat", "shamir_reveal_flat",
           "flash_attention", "flash_attention_bwd"]


def _pad_to(x, multiple, axis, value=0.0):
    n = x.shape[axis]
    rem = (-n) % multiple
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad, constant_values=value)


def gram_hessian(X, w, block_n: int = 512):
    """X^T diag(w) X with automatic N/d padding (padded rows get w = 0)."""
    n, d = X.shape
    bn = _block(n, block_n)
    Xp = _pad_to(_pad_to(X, bn, 0), 128, 1)
    wp = _pad_to(w, bn, 0)  # zero weight rows contribute nothing
    H = gram_hessian_pallas(Xp, wp, block_n=bn)
    return H[:d, :d]


def _use_simulation(simulate: bool | None) -> bool:
    """The XLA simulation runs where the kernels interpret (the CPU)
    unless a test forces the real kernel through the interpreter with
    ``simulate=False``; on a TPU the compiled kernel always runs."""
    interpret = interpret_kernels()
    if simulate is None:
        return interpret
    if simulate and not interpret:
        raise ValueError("the XLA simulation is the interpreter's stand-in; "
                         "a TPU runs the compiled kernel")
    return simulate


def _kernel_operands(X, mxu_operand, y, bn):
    """Pad N to ``bn`` and d to 128; the compiled kernel takes f32 only.

    Returns (payload, mxu_operand, y) for the kernel.  Mosaic has no
    64-bit types, so a compiled launch streams the f32 MXU operand as
    the payload too (on a TPU they are the same array).
    """
    Xm = X.astype(jnp.float32) if mxu_operand is None else mxu_operand
    Xmp = _pad_to(_pad_to(Xm, bn, 1), 128, 2)
    yp = _pad_to(y, bn, 1)
    if interpret_kernels():
        return _pad_to(_pad_to(X, bn, 1), 128, 2), Xmp, yp
    return Xmp, Xmp, yp.astype(jnp.float32)


def _block(n: int, block_n: int) -> int:
    return min(block_n, int(np.ceil(n / 8) * 8)) if n < block_n else block_n


def fused_irls(beta, X, y, counts=None, block_n: int = 512,
               mxu_operand=None, simulate: bool | None = None):
    """Batched masked IRLS summaries: (H (S,d,d) f32, g (S,d), dev (S,)).

    X: (S, N_max, d); y: (S, N_max); counts: (S,) true (ragged) row counts,
    default N_max everywhere.  Pads N_max to a block multiple and d to 128
    (row masking makes the N padding exact; zero d-columns are benign and
    sliced off).  ``mxu_operand`` is the pre-cast f32 copy of X fed to the
    Gram matmul — pass it from a hot loop to cast once instead of per call;
    on TPU X is already f32 and the two operands are the same array.

    On the CPU the kernel's numerics contract runs as plain XLA ops
    (``fused_irls_sim``): the Pallas interpreter's per-program
    whole-operand copies dominate at production N.  ``simulate=False``
    forces the real kernel through the interpreter (tests do, to pin
    kernel == simulation).  On a TPU the compiled kernel always runs; with
    a float64 payload it streams the f32 operand for the Gram, and the
    gradient and deviance — which fix the Newton fixed point — keep the
    simulation's float64 terms, as XLA ops beside the kernel.
    """
    s_dim, n, d = X.shape
    if counts is None:
        counts = jnp.full((s_dim,), n, jnp.int32)
    counts = counts.astype(jnp.int32)
    if _use_simulation(simulate):
        Xm = X.astype(jnp.float32) if mxu_operand is None else mxu_operand
        return fused_irls_sim(beta, X, Xm, y, counts)
    bn = _block(n, block_n)
    Xp, Xmp, yp = _kernel_operands(X, mxu_operand, y, bn)
    H, g, dev = fused_irls_pallas(
        _pad_to(beta, 128, 0).astype(Xp.dtype), Xp, Xmp, yp, counts,
        block_n=bn,
    )
    H = H[:, :d, :d]
    if Xp.dtype == X.dtype:
        return H, g[:, :d], dev
    _, g, dev = _sim_terms(beta, X, y, counts)
    return H, g, dev


def fused_irls_cv(betas, X, y, fold_ids, fold_of, counts=None,
                  block_n: int = 512, mxu_operand=None,
                  simulate: bool | None = None):
    """Cross-validated batched IRLS summaries over a (config, institution)
    grid: (H (C,S,d,d) f32, g (C,S,d), dev_train (C,S), dev_val (C,S),
    correct_val (C,S), count_val (C,S)).

    ``betas`` is (C, d) — one iterate per (lambda x fold) path config;
    ``fold_ids`` is (S, N_max) int32 per-row fold assignment and
    ``fold_of`` (C,) the held-out fold per config (-1 = none, i.e. a
    full-data fit sharing the launch).  Same padding, ``simulate`` and
    payload-precision semantics as ``fused_irls``: rows beyond ``counts``
    are masked regardless of their fold id, so N/d padding is exact.
    """
    s_dim, n, d = X.shape
    if counts is None:
        counts = jnp.full((s_dim,), n, jnp.int32)
    counts = counts.astype(jnp.int32)
    fold_ids = fold_ids.astype(jnp.int32)
    fold_of = fold_of.astype(jnp.int32)
    if _use_simulation(simulate):
        Xm = X.astype(jnp.float32) if mxu_operand is None else mxu_operand
        return fused_irls_cv_sim(betas, X, Xm, y, counts, fold_ids, fold_of)
    bn = _block(n, block_n)
    Xp, Xmp, yp = _kernel_operands(X, mxu_operand, y, bn)
    fidp = _pad_to(fold_ids, bn, 1)  # padded rows are row-masked anyway
    H, g, dtr, dva, acc, nva = fused_irls_cv_pallas(
        _pad_to(betas, 128, 1).astype(Xp.dtype), Xp, Xmp, yp, counts,
        fidp, fold_of, block_n=bn,
    )
    H = H[:, :, :d, :d]
    if Xp.dtype == X.dtype:
        return H, g[:, :, :d], dtr, dva, acc, nva
    _, g, dtr, dva, acc, nva = _cv_sim_terms(
        betas, X, y, counts, fold_ids, fold_of
    )
    return H, g, dtr, dva, acc, nva


def shamir_shares(
    secret: jnp.ndarray,  # (n,) uint32 or uint64, reduced mod modulus
    coeffs: jnp.ndarray,  # (t-1, n) same dtype, reduced
    num_shares: int,
    modulus: int,
) -> jnp.ndarray:
    """(num_shares, n) shares; 32-bit limb kernel (TPU has no 64-bit VPU)."""
    assert modulus < 2**31, "kernel field elements must fit 31 bits"
    n = secret.shape[0]
    rows_pad, block_rows = _flat_blocking(max(1, int(np.ceil(n / 128))))
    total = rows_pad * 128

    def to_tile(x):
        flat = jnp.pad(x.astype(jnp.uint32), (0, total - n))
        return flat.reshape(rows_pad, 128)

    secret_t = to_tile(secret)
    coeffs_t = jnp.stack([to_tile(c) for c in coeffs], axis=0)
    out = shamir_poly_pallas(
        secret_t, coeffs_t, num_shares, modulus, block_rows=block_rows,
    )
    return out.reshape(num_shares, total)[:, :n].astype(secret.dtype)


def _flat_blocking(rows: int) -> tuple[int, int]:
    """(rows_padded, block_rows) for an already (rows, 128)-tiled buffer.

    Interpret mode runs the grid as a Python loop, so a single whole-buffer
    program minimizes dispatch overhead; compiled TPU mode tiles to VMEM-
    sized blocks.
    """
    if interpret_kernels():
        return rows, rows
    block_rows = min(256, rows)
    rows_pad = int(np.ceil(rows / block_rows) * block_rows)
    return rows_pad, block_rows


def _pad_rows(x, rows_pad, axis):
    pad = rows_pad - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def shamir_protect_flat(
    buf: jnp.ndarray,  # (rows, 128) float payload tiles
    coeffs: jnp.ndarray,  # (R, t-1, rows, 128) uint32, reduced per residue
    num_shares: int,
    moduli: tuple[int, ...],
    frac_bits: int,
    points: tuple[int, ...] | None = None,
) -> jnp.ndarray:
    """Fused fixed-point encode + share of a flat buffer in ONE launch.

    Returns (len(points), R, rows, 128) uint32 — the holder axis leads so
    a Computation Center's slice is ``out[j]``.  ``points`` (default the
    full 1..num_shares fan-out) selects which public evaluation points are
    emitted: the in-SPMD ``secure_psum`` wire only transmits a threshold
    subset, so it asks for exactly those slices and the kernel never
    evaluates the rest.  Zero-padded tail rows encode to zero shares
    (benign through aggregate/reveal).
    """
    rows = buf.shape[0]
    rows_pad, block_rows = _flat_blocking(rows)
    bufp = _pad_rows(buf, rows_pad, 0)
    coeffsp = _pad_rows(coeffs, rows_pad, 2)
    if coeffsp.shape[1] == 0:  # t = 1: a zero high coefficient is a no-op
        coeffsp = jnp.zeros(
            (coeffs.shape[0], 1) + bufp.shape, dtype=jnp.uint32
        )
    out = shamir_encode_share_pallas(
        bufp, coeffsp, num_shares, tuple(moduli), frac_bits,
        block_rows=block_rows,
        points=tuple(points) if points is not None else None,
    )  # (R, len(points), rows_pad, 128)
    return jnp.swapaxes(out, 0, 1)[:, :, :rows]


def shamir_reveal_flat(
    shares: jnp.ndarray,  # (k, R, rows, 128) uint32 aggregate share slices
    points: tuple[int, ...],  # public 1-based holder ids of the k slices
    moduli: tuple[int, ...],
    frac_bits: int,
) -> jnp.ndarray:
    """Fused Lagrange reconstruction + CRT decode -> (rows, 128) float64.

    The modular hot loop (k multiply-adds per residue + the Garner digit)
    runs in one kernel launch; only the final uint64 recombination and the
    fixed-point rescale are host-graph elementwise ops.
    """
    k, num_residues, rows = shares.shape[0], shares.shape[1], shares.shape[2]
    assert len(points) == k
    rows_pad, block_rows = _flat_blocking(rows)
    stacked = _pad_rows(jnp.swapaxes(shares, 0, 1), rows_pad, 2)
    lams = lagrange_weights_host(tuple(points), tuple(moduli))
    garner = num_residues == 2
    rec = shamir_reconstruct_pallas(
        stacked, lams, tuple(moduli), garner=garner, block_rows=block_rows,
    )[:, :rows]  # (R, rows, 128)
    modulus_product = 1
    for p in moduli:
        modulus_product *= p
    half = jnp.uint64((modulus_product - 1) // 2)
    if garner:
        # x = r1 + p1 * k_digit < p1*p2 < 2**62: exact in uint64
        x = rec[0].astype(jnp.uint64) + jnp.uint64(moduli[0]) * rec[1].astype(
            jnp.uint64
        )
    else:
        x = rec[0].astype(jnp.uint64)
    neg = -((jnp.uint64(modulus_product) - x).astype(jnp.int64))
    signed = jnp.where(x <= half, x.astype(jnp.int64), neg)
    return signed.astype(jnp.float64) / jnp.float64(1 << frac_bits)


def shamir_reconstruct(
    secret_shares: jnp.ndarray,  # (k, n) uint32/uint64, reduced mod modulus
    points,  # 1-based evaluation points of the k share rows
    modulus: int,
) -> jnp.ndarray:
    """(n,) reconstructed secret — per-residue mirror of shamir_shares."""
    assert modulus < 2**31, "kernel field elements must fit 31 bits"
    k, n = secret_shares.shape
    rows = max(1, int(np.ceil(n / 128)))
    rows_pad, block_rows = _flat_blocking(rows)
    total = rows_pad * 128
    flat = jnp.pad(secret_shares.astype(jnp.uint32), ((0, 0), (0, total - n)))
    tiles = flat.reshape(1, k, rows_pad, 128)
    lams = lagrange_weights_host(tuple(points), (modulus,))
    out = shamir_reconstruct_pallas(
        tiles, lams, (modulus,), garner=False, block_rows=block_rows,
    )
    return out.reshape(total)[:n].astype(secret_shares.dtype)


def flash_attention(q, k, v, block_q: int = 512, block_k: int = 512):
    """Causal GQA flash attention.  q: (B, S, H, D); k/v: (B, S, KVH, D).

    Pads S to a block multiple and D to 128; GQA mapped in the kernel
    index map (no KV broadcast in HBM).  Same semantics as
    ref.flash_attention.
    """
    from .flash_attention import flash_attention_pallas

    B, S, H, D = q.shape
    KVH = k.shape[2]
    group = H // KVH
    bq = min(block_q, max(8, int(np.ceil(S / 8) * 8)))
    bk = min(block_k, bq)
    s_pad = int(np.ceil(S / max(bq, bk)) * max(bq, bk))
    d_pad = int(np.ceil(D / 128) * 128)

    def prep(t, heads):
        t = jnp.pad(t, ((0, 0), (0, s_pad - S), (0, 0), (0, d_pad - D)))
        return jnp.moveaxis(t, 2, 1).reshape(B * heads, s_pad, d_pad)

    qp, kp, vp = prep(q, H), prep(k, KVH), prep(v, KVH)
    # padded D columns are zero => contribute nothing to scores; the
    # kernel normalizes with the true seq_len mask.
    scale_fix = (d_pad / D) ** 0.5  # kernel scales by d_pad**-0.5
    o, m, l = flash_attention_pallas(
        qp * scale_fix, kp, vp, group=group, seq_len=S,
        block_q=bq, block_k=bk,
    )
    o = o.reshape(B, H, s_pad, d_pad)[:, :, :S, :D]
    return jnp.moveaxis(o, 1, 2)


def flash_attention_bwd(q, k, v, do, block_q: int = 512,
                        block_k: int = 512):
    """Flash backward: (dq, dk, dv) for causal GQA attention.

    q/do: (B, S, H, D); k/v: (B, S, KVH, D).  Re-runs the fwd kernel for
    (o, m, l) — in a fused deployment those come from the saved forward —
    then the dq and dk/dv kernels.  Oracle: jax.grad of ref.flash_attention.
    """
    from .flash_attention import flash_attention_pallas
    from .flash_attention_bwd import flash_dkdv_pallas, flash_dq_pallas

    B, S, H, D = q.shape
    KVH = k.shape[2]
    group = H // KVH
    bq = min(block_q, max(8, int(np.ceil(S / 8) * 8)))
    bk = min(block_k, bq)
    s_pad = int(np.ceil(S / max(bq, bk)) * max(bq, bk))
    d_pad = int(np.ceil(D / 128) * 128)

    def prep(t, heads):
        t = jnp.pad(t, ((0, 0), (0, s_pad - S), (0, 0), (0, d_pad - D)))
        return jnp.moveaxis(t, 2, 1).reshape(B * heads, s_pad, d_pad)

    scale_fix = (d_pad / D) ** 0.5
    qp = prep(q, H) * scale_fix
    kp, vp, dop = prep(k, KVH), prep(v, KVH), prep(do, H)
    o, m, l = flash_attention_pallas(
        qp, kp, vp, group=group, seq_len=S, block_q=bq, block_k=bk,
    )
    linv = 1.0 / jnp.maximum(l, 1e-30)
    delta = jnp.sum(dop.astype(jnp.float32) * o.astype(jnp.float32), -1)
    args = (qp, kp, vp, dop, m, linv, delta)
    kw = dict(group=group, seq_len=S, block_q=bq, block_k=bk)
    dq = flash_dq_pallas(*args, **kw)
    dk, dv = flash_dkdv_pallas(*args, **kw)

    def unprep(t, heads):
        t = t.reshape(B, heads, s_pad, d_pad)[:, :, :S, :D]
        return jnp.moveaxis(t, 1, 2)

    # undo the d-pad rescale on dq (dq carries one factor of scale)
    return (unprep(dq, H) * scale_fix, unprep(dk, KVH), unprep(dv, KVH))
