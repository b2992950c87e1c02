"""Pallas TPU kernel: the paper's full per-institution IRLS local phase,
batched over institutions, in ONE streaming pass over X.

Per Newton iteration every institution j computes (Algorithm 1, steps 4-6)

    z = X_j beta,  p = sigmoid(z),  w = p (1 - p)
    H_j = X_j^T diag(w) X_j          (Eq. 4, O(N d^2) — the hot term)
    g_j = X_j^T (y_j - p)            (Eq. 5)
    dev_j = -2 sum(y z - softplus z) (Eq. 6)

The pre-fusion pipeline ran three separate passes (z/g/dev kernel, then a
weighted-Gram kernel re-reading X with w round-tripped through HBM) and a
Python loop over institutions.  Here one kernel with grid (S, N/block_n)
streams each institution's (block_n, d) tile through VMEM exactly once and
emits all three summaries for all S institutions; the IRLS weights live
only in VMEM registers between the sigmoid and the Gram update — they are
never written to HBM.

Ragged institutions are padded to a common N_max and masked inside the
kernel with per-institution row counts, so one launch covers uneven
partition sizes (the paper's horizontal split is never exactly even).

Precision contract: the Gram/Hessian accumulates in float32 on the MXU
(`mxu_ref` is a separate operand so a CPU/interpret profile can keep the
main payload in float64 — on TPU both refs alias one f32 array).  The
gradient/deviance accumulate in the payload dtype.  H only preconditions
the Newton step — the fixed point solves g(beta) = lam beta — so f32 H
changes the trajectory, not the answer; g/dev precision is what bounds the
final beta and the deviance-based convergence test.  Mosaic has no 64-bit
types, so a compiled launch sees f32 operands only: with a float64 payload
``ops.fused_irls`` takes the Gram from the kernel and the float64 g/dev
from the pack's bf16 slices of X (``sliced_terms``), exact MXU dots
combined in float64.

TPU layout: labels (and fold ids) stream as lane-dense (1, block_n) rows,
per-institution counts (and held-out folds) ride in SMEM as scalar
prefetch, and the small outputs are (1, d) / (1, 128) rows — every block
obeys the (8, 128) tiling rule.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import ZERO, resolve_interpret

__all__ = ["fused_irls_pallas", "fused_irls_sim", "fused_irls_cv_pallas",
           "fused_irls_cv_sim", "gram_hessian_pallas"]

DEFAULT_BLOCK_N = 512

# f32 matmuls at full f32 precision on the MXU (the TPU default would
# round operands to bfloat16); a no-op for the interpreter's f64 payload.
_HIGHEST = jax.lax.Precision.HIGHEST


def _row_dot(a, b):
    """(1, n) @ (n, m) -> (1, m) in a's dtype."""
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=a.dtype,
    )


def _linear_row(beta, x):
    """z = x beta as a lane-dense (1, block_n) row: beta (1, d) . x^T."""
    return jax.lax.dot_general(
        beta, x, (((1,), (1,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=x.dtype,
    )


def _weighted_gram(xm, w):
    """X^T diag(w) X from the f32 MXU operand; ``w`` a (1, block_n) row."""
    return jax.lax.dot_general(
        xm.T * w.astype(jnp.float32), xm, (((1,), (0,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=jnp.float32,
    )


def _irls_kernel(cnt_ref, beta_ref, x_ref, xm_ref, y_ref,
                 h_ref, g_ref, dev_ref, *, block_n):
    s = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)
        g_ref[...] = jnp.zeros_like(g_ref)
        dev_ref[...] = jnp.zeros_like(dev_ref)

    x = x_ref[0]  # (block_n, d) payload dtype
    y = y_ref[0]  # (1, block_n) lane-dense labels
    beta = beta_ref[...].astype(x.dtype)  # (1, d)
    # ragged mask: absolute row id vs this institution's true row count
    row = i * block_n + jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1)
    valid = (row < cnt_ref[s]).astype(x.dtype)  # (1, block_n)

    z = _linear_row(beta, x)  # (1, block_n)
    p = jax.nn.sigmoid(z)
    w = (p * (1.0 - p)) * valid  # IRLS weights: VMEM-resident only
    g_ref[0] += _row_dot((y - p) * valid, x)
    softplus = jnp.logaddexp(jnp.zeros_like(z), z)
    dev_ref[0] += -2.0 * jnp.sum((y * z - softplus) * valid)
    h_ref[0] += _weighted_gram(xm_ref[0], w)  # MXU Gram update in f32


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def fused_irls_pallas(
    beta: jnp.ndarray,  # (d,)
    X: jnp.ndarray,  # (S, N_max, d) payload dtype (f32 on TPU)
    Xm: jnp.ndarray,  # (S, N_max, d) float32 MXU operand (== X on TPU)
    y: jnp.ndarray,  # (S, N_max) payload dtype
    counts: jnp.ndarray,  # (S,) int32 true row counts (<= N_max)
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool | None = None,
):
    """All-institution summaries in one launch.

    Returns (H (S, d, d) f32, g (S, d), dev (S,)); g/dev in X.dtype.
    N_max % block_n == 0 and d % 128 == 0 (ops.py pads); rows >= counts[s]
    are masked out, so tail padding may hold anything.  Compiled, a
    multi-block ``block_n`` must be a multiple of 128: labels stream as
    lane-dense (1, block_n) rows, and the row counts ride in SMEM as
    scalar prefetch.
    """
    s_dim, n, d = X.shape
    assert n % block_n == 0, "caller pads N_max"
    kernel = functools.partial(_irls_kernel, block_n=block_n)
    H, g, dev = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s_dim, n // block_n),
            in_specs=[
                pl.BlockSpec((1, d), lambda s, i, c: (ZERO, ZERO)),
                pl.BlockSpec((1, block_n, d), lambda s, i, c: (s, i, ZERO)),
                pl.BlockSpec((1, block_n, d), lambda s, i, c: (s, i, ZERO)),
                pl.BlockSpec((1, 1, block_n), lambda s, i, c: (s, ZERO, i)),
            ],
            out_specs=[
                pl.BlockSpec((1, d, d), lambda s, i, c: (s, ZERO, ZERO)),
                pl.BlockSpec((1, 1, d), lambda s, i, c: (s, ZERO, ZERO)),
                pl.BlockSpec((1, 1, 128), lambda s, i, c: (s, ZERO, ZERO)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((s_dim, d, d), jnp.float32),
            jax.ShapeDtypeStruct((s_dim, 1, d), X.dtype),
            jax.ShapeDtypeStruct((s_dim, 1, 128), X.dtype),
        ],
        interpret=resolve_interpret(interpret),
        name="fused_irls_pallas",
    )(counts.astype(jnp.int32), beta.reshape(1, d), X, Xm,
      y.reshape(s_dim, 1, n))
    return H, g[:, 0], dev[:, 0, 0]


def _sim_terms(beta, X, y, counts):
    """The simulation's f64 z/g/dev terms and the f32 IRLS weights: f64
    contractions over X, which a TPU emulates (``sliced_terms`` is what
    a compiled launch with a float64 payload and cut slices uses)."""
    with jax.named_scope("f64_terms"):
        n = X.shape[1]
        mask = (
            jnp.arange(n, dtype=jnp.int32)[None, :] < counts[:, None]
        ).astype(jnp.float64)
        if X.dtype == jnp.float32:
            z = jax.lax.dot_general(
                X, beta.astype(jnp.float32), (((2,), (0,)), ((), ())),
                preferred_element_type=jnp.float64,
            )
        else:
            z = jnp.einsum("snd,d->sn", X, beta.astype(X.dtype))
        p = jax.nn.sigmoid(z)
        w32 = ((p * (1.0 - p)) * mask).astype(jnp.float32)
        resid = (y - p) * mask
        if X.dtype == jnp.float32:
            r32 = resid.astype(jnp.float32)
            g = jnp.stack([
                jax.lax.dot_general(
                    r32[j], X[j], (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float64,
                )
                for j in range(X.shape[0])
            ])
        else:
            g = jnp.einsum("snd,sn->sd", X, resid)
        dev = -2.0 * jnp.sum((y * z - jnp.logaddexp(0.0, z)) * mask, axis=1)
        return w32, g, dev


@jax.jit
def fused_irls_sim(beta, X, Xm, y, counts):
    """Functional simulation of ``fused_irls_pallas`` — same numerics
    contract (f32 Gram accumulation from the MXU operand, g/dev in the
    payload dtype, row masks), evaluated as plain XLA ops.

    This is what the CPU runs at production sizes: the Pallas
    interpreter emulates every grid program with whole-operand
    copies, which at (S, 2e5, d) costs ~6x the arithmetic itself on CPU.
    The blocked kernel remains the compiled TPU path; tests pin the two
    against each other (they differ only in f32 summation order).

    One deliberate upgrade over the kernel: with a float32 payload the
    kernel accumulates g/dev in f32 (the hardware dtype); the sim
    always accumulates them in f64 (free on CPU via
    ``preferred_element_type``), which keeps the secure protocol's
    fixed-point codec the dominant error term.  The kernel == sim
    pinning test therefore runs with an f64 payload, where the two
    contracts coincide.

    Two contraction styles, each where the CPU backend is fastest: the
    O(N d) z/g/dev reductions run batched (or, for the mixed
    f32-operand/f64-accumulation case, unrolled — the batched form hits
    a ~10x-slow generic emitter), while the O(N d^2) Gram unrolls into
    per-institution 2D contractions mirroring the kernel's (S, blocks)
    grid; the batched (S, N, d) dot emitter is ~40% slower with much
    higher variance.  The 3-operand einsum folds the IRLS row scaling
    into the Gram contraction instead of materializing a scaled copy of
    Xm.
    """
    w32, g, dev = _sim_terms(beta, X, y, counts)
    return _sim_gram(w32, Xm), g, dev


def _sim_gram(w32, Xm):
    """The simulation's f32 Gram, X^T diag(w) X per institution."""
    with jax.named_scope("gram"):
        return jnp.stack([
            jnp.einsum(
                "n,ni,nj->ij", w32[j], Xm[j], Xm[j],
                preferred_element_type=jnp.float32,
            )
            for j in range(Xm.shape[0])
        ])


# -- cross-validated variant: fold masks composed into the row masks ---------
#
# The selection subsystem advances C = (lambda x fold) path points at once.
# Config c trains on every row whose fold id differs from fold_of[c] and
# evaluates held-out deviance/accuracy on the rows it excludes — the fold
# mask composes with the ragged row-count mask INSIDE the kernel, so one
# streaming pass over the same packed (S, N_max, d) batch emits train-fold
# summaries AND validation metrics for every (config, institution) pair
# without ever materializing per-fold repacks of X.  fold_of[c] == -1
# means "no held-out fold" (a full-data path fit riding in the same batch:
# fold ids are never negative, so the val mask is empty and the train mask
# reduces to the plain row mask).

def _irls_cv_kernel(cnt_ref, fold_ref, beta_ref, x_ref, xm_ref, y_ref,
                    fid_ref, h_ref, g_ref, dtr_ref, dva_ref, acc_ref,
                    nva_ref, *, block_n):
    c = pl.program_id(0)
    s = pl.program_id(1)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)
        g_ref[...] = jnp.zeros_like(g_ref)
        dtr_ref[...] = jnp.zeros_like(dtr_ref)
        dva_ref[...] = jnp.zeros_like(dva_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        nva_ref[...] = jnp.zeros_like(nva_ref)

    x = x_ref[0]  # (block_n, d) payload dtype
    y = y_ref[0]  # (1, block_n) lane-dense labels
    beta = beta_ref[0].astype(x.dtype)  # (1, d) — this config's iterate
    row = i * block_n + jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1)
    valid = row < cnt_ref[s]  # ragged row mask
    hold = jnp.logical_and(valid, fid_ref[0] == fold_ref[c])
    tmask = jnp.logical_and(valid, jnp.logical_not(hold)).astype(x.dtype)
    vmask = hold.astype(x.dtype)

    z = _linear_row(beta, x)  # (1, block_n)
    p = jax.nn.sigmoid(z)
    w = (p * (1.0 - p)) * tmask  # train-fold IRLS weights, VMEM-only
    g_ref[0, 0] += _row_dot((y - p) * tmask, x)
    ll = y * z - jnp.logaddexp(jnp.zeros_like(z), z)
    dtr_ref[0, 0] += -2.0 * jnp.sum(ll * tmask)
    dva_ref[0, 0] += -2.0 * jnp.sum(ll * vmask)
    correct = (z > 0.0) == (y > 0.5)
    acc_ref[0, 0] += jnp.sum(jnp.where(correct, vmask, 0.0))
    nva_ref[0, 0] += jnp.sum(vmask)
    h_ref[0, 0] += _weighted_gram(xm_ref[0], w)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def fused_irls_cv_pallas(
    betas: jnp.ndarray,  # (C, d) one iterate per path config
    X: jnp.ndarray,  # (S, N_max, d) payload dtype (f32 on TPU)
    Xm: jnp.ndarray,  # (S, N_max, d) float32 MXU operand (== X on TPU)
    y: jnp.ndarray,  # (S, N_max) payload dtype
    counts: jnp.ndarray,  # (S,) int32 true row counts (<= N_max)
    fold_ids: jnp.ndarray,  # (S, N_max) int32 per-row fold assignment
    fold_of: jnp.ndarray,  # (C,) int32 held-out fold per config (-1: none)
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool | None = None,
):
    """Every (config, institution) train summary + held-out metric in one
    launch: H (C, S, d, d) f32, g (C, S, d), dev_train (C, S),
    dev_val (C, S), correct_val (C, S), count_val (C, S); g and the
    scalar reductions in X.dtype.  Grid (C, S, N/block_n): X streams
    through VMEM once per config with the fold mask applied in-register.
    Blocking and layout follow ``fused_irls_pallas``; ``counts`` and
    ``fold_of`` are the scalar prefetch.
    """
    c_dim = betas.shape[0]
    s_dim, n, d = X.shape
    assert n % block_n == 0, "caller pads N_max"
    kernel = functools.partial(_irls_cv_kernel, block_n=block_n)
    scalar_spec = pl.BlockSpec((1, 1, 1, 128),
                               lambda c, s, i, *_: (c, s, ZERO, ZERO))
    scalar = jax.ShapeDtypeStruct((c_dim, s_dim, 1, 128), X.dtype)
    row_spec = pl.BlockSpec((1, 1, block_n),
                            lambda c, s, i, *_: (s, ZERO, i))
    tile_spec = pl.BlockSpec((1, block_n, d),
                             lambda c, s, i, *_: (s, i, ZERO))
    H, g, *stats = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(c_dim, s_dim, n // block_n),
            in_specs=[
                pl.BlockSpec((1, 1, d), lambda c, s, i, *_: (c, ZERO, ZERO)),
                tile_spec,
                tile_spec,
                row_spec,
                row_spec,
            ],
            out_specs=[
                pl.BlockSpec((1, 1, d, d),
                             lambda c, s, i, *_: (c, s, ZERO, ZERO)),
                pl.BlockSpec((1, 1, 1, d),
                             lambda c, s, i, *_: (c, s, ZERO, ZERO)),
                scalar_spec, scalar_spec, scalar_spec, scalar_spec,
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((c_dim, s_dim, d, d), jnp.float32),
            jax.ShapeDtypeStruct((c_dim, s_dim, 1, d), X.dtype),
            scalar, scalar, scalar, scalar,
        ],
        interpret=resolve_interpret(interpret),
        name="fused_irls_cv_pallas",
    )(counts.astype(jnp.int32), fold_of.astype(jnp.int32),
      betas.reshape(c_dim, 1, d), X, Xm, y.reshape(s_dim, 1, n),
      fold_ids.astype(jnp.int32).reshape(s_dim, 1, n))
    return (H, g[:, :, 0], *(v[:, :, 0, 0] for v in stats))


def _cv_sim_terms(betas, X, y, counts, fold_ids, fold_of):
    """The CV simulation's f64 terms: train weights (f32), g, train/val
    deviance, held-out correct and count."""
    with jax.named_scope("f64_terms"):
        s_dim, n = X.shape[0], X.shape[1]
        row_ok = jnp.arange(n, dtype=jnp.int32)[None, :] < counts[:, None]
        on_fold = fold_ids[None] == fold_of[:, None, None]  # (C, S, N)
        hold = row_ok[None] & on_fold
        tmask = (row_ok[None] & ~on_fold).astype(jnp.float64)
        vmask = hold.astype(jnp.float64)
        z = jax.lax.dot_general(
            X, betas.astype(X.dtype), (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float64,
        )  # (S, N, C)
        z = jnp.moveaxis(z, -1, 0)  # (C, S, N)
        p = jax.nn.sigmoid(z)
        ll = y[None] * z - jnp.logaddexp(0.0, z)
        dev_tr = -2.0 * jnp.sum(ll * tmask, axis=2)
        dev_va = -2.0 * jnp.sum(ll * vmask, axis=2)
        acc_va = jnp.sum(
            jnp.where((z > 0.0) == (y[None] > 0.5), vmask, 0.0), axis=2
        )
        n_va = jnp.sum(vmask, axis=2)
        resid = (y[None] - p) * tmask  # (C, S, N) f64
        g = jnp.stack([
            jax.lax.dot_general(
                resid[:, s], X[s], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float64,
            )
            for s in range(s_dim)
        ], axis=1)  # (C, S, d)
        w32 = ((p * (1.0 - p)) * tmask).astype(jnp.float32)
        return w32, g, dev_tr, dev_va, acc_va, n_va


@jax.jit
def fused_irls_cv_sim(betas, X, Xm, y, counts, fold_ids, fold_of):
    """Functional simulation of ``fused_irls_cv_pallas`` as plain XLA ops
    — the CPU/interpret execution shape at production N, mirroring
    ``fused_irls_sim``'s contracts: f32 Gram accumulation from the MXU
    operand, f64 gradient/deviance accumulation regardless of payload
    dtype, fold∘row masks identical to the kernel.

    Contraction styles follow the same CPU-emitter measurements as the
    non-CV sim: the O(C S N d) z/g reductions run as clean 2D gemms
    (z batched over configs, g unrolled per institution), while the
    O(C S N d^2) Gram — the flop wall — runs as a ``lax.map`` over the
    config axis of per-institution 2D contractions, so the traced graph
    stays small at any path length while each contraction hits the fast
    gemm emitter.
    """
    s_dim = X.shape[0]
    w32, g, dev_tr, dev_va, acc_va, n_va = _cv_sim_terms(
        betas, X, y, counts, fold_ids, fold_of
    )

    def gram_one_config(w_c):  # (S, N) f32 -> (S, d, d) f32
        return jnp.stack([
            jax.lax.dot_general(
                Xm[s] * w_c[s][:, None], Xm[s],
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            for s in range(s_dim)
        ])

    with jax.named_scope("gram"):
        H = jax.lax.map(gram_one_config, w32)  # (C, S, d, d)
    return H, g, dev_tr, dev_va, acc_va, n_va


# -- explicit-weight Gram (legacy public op) ---------------------------------
def _gram_kernel(x_ref, w_ref, o_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += _weighted_gram(x_ref[...].astype(jnp.float32), w_ref[...])


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def gram_hessian_pallas(
    X: jnp.ndarray, w: jnp.ndarray, block_n: int = DEFAULT_BLOCK_N,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """X^T diag(w) X for caller-supplied weights (X: (N, d), N % block_n
    == 0, d % 128 == 0 — ops.py pads).  The secure-fit hot path derives w
    from beta inside ``fused_irls_pallas`` instead; this variant stays for
    workloads that reweight rows externally (e.g. offset/exposure models).
    The weights stream as a lane-dense (1, N) row.
    """
    n, d = X.shape
    assert n % block_n == 0, "caller pads N"
    return pl.pallas_call(
        _gram_kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, ZERO)),
            pl.BlockSpec((1, block_n), lambda i: (ZERO, i)),
        ],
        out_specs=pl.BlockSpec((d, d), lambda i: (ZERO, ZERO)),
        out_shape=jax.ShapeDtypeStruct((d, d), jnp.float32),
        interpret=resolve_interpret(interpret),
        name="gram_hessian_pallas",
    )(X, w.reshape(1, n))
