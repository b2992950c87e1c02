"""Pallas TPU kernels: causal flash attention backward (dq / dk / dv).

Completes the kernel story started by flash_attention.py: the backward
recomputes block scores from (q, k) and the saved softmax statistics
(m, l) — residuals stay O(S·D) and the (bq, bk) score/ds tiles never
leave VMEM.  Two kernels, each with a sequential minor grid axis feeding
a VMEM accumulator:

  * ``_dq_kernel``   grid (B·H,  nq, nk): dq_i   += ds_ij @ k_j
  * ``_dkdv_kernel`` grid (B·KVH, nk, nq·G): dk_j += ds_ijᵀ @ q_i,
                     dv_j += p_ijᵀ @ do_i  — GQA group members are
                     walked in the minor axis so dk/dv accumulate the
                     group sum in scratch (no G× HBM partials).

where  p_ij = exp(q_i k_jᵀ·scale − m_i) / l_i  (causal-masked) and
``ds_ij = p_ij ∘ (do_i v_jᵀ − delta_i)``, delta = Σ_d do∘o precomputed
host-side (one cheap fused reduce).

ops.flash_attention_bwd is the jit'd wrapper; the oracle is
``jax.grad`` of ref.flash_attention (tests/test_kernels_flash.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret

__all__ = ["flash_dq_pallas", "flash_dkdv_pallas"]

NEG_INF = -1e30


def _block_tiles(q, k, v, do, scale, qi_pos, kj_pos, m, linv, seq_len):
    """Shared per-(q block, k block) backward math.  All f32.

    q/do: (bq, D); k/v: (bk, D); m/linv: (bq, 1).
    Returns (p, ds): (bq, bk) each.
    """
    s = jax.lax.dot_general(
        q * scale, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    mask = (kj_pos <= qi_pos) & (kj_pos < seq_len)
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - m) * linv
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return p, p * dp


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, m_ref, linv_ref, delta_ref,
               o_ref, dq_scr, *, scale, block_q, block_k, nk, seq_len):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    qi_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kj_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    @pl.when(ki * block_k <= qi * block_q + block_q - 1)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        p, pdp = _block_tiles(q, k, v, do, scale, qi_pos, kj_pos,
                              m_ref[0][:, None], linv_ref[0][:, None],
                              seq_len)
        ds = pdp - p * delta_ref[0][:, None]
        dq_scr[...] += scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = dq_scr[...].astype(o_ref.dtype)


def _dkdv_kernel(q_ref, k_ref, v_ref, do_ref, m_ref, linv_ref, delta_ref,
                 dk_ref, dv_ref, dk_scr, dv_scr, *, scale, block_q,
                 block_k, n_minor, group, seq_len):
    ki = pl.program_id(1)
    mi = pl.program_id(2)  # walks (g, q_block) pairs
    nq = n_minor // group
    qi = mi % nq

    @pl.when(mi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    qi_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kj_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    @pl.when(ki * block_k <= qi * block_q + block_q - 1)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        p, pdp = _block_tiles(q, k, v, do, scale, qi_pos, kj_pos,
                              m_ref[0][:, None], linv_ref[0][:, None],
                              seq_len)
        ds = pdp - p * delta_ref[0][:, None]
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_scr[...] += scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(mi == n_minor - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "group", "seq_len", "block_q", "block_k", "interpret"))
def flash_dq_pallas(q, k, v, do, m, linv, delta, group, seq_len,
                    block_q=512, block_k=512, interpret=None):
    """dq: q/do (B*H, S, D); k/v (B*KVH, S, D); m/linv/delta (B*H, S)."""
    BH, S, D = q.shape
    nq, nk = S // block_q, S // block_k
    scale = D**-0.5
    kernel = functools.partial(
        _dq_kernel, scale=scale, block_q=block_q, block_k=block_k, nk=nk,
        seq_len=seq_len)
    stat = pl.BlockSpec((1, block_q), lambda b, i, j: (b, i))
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda b, i, j, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda b, i, j, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            stat, stat, stat,
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(q, k, v, do, m, linv, delta)


@functools.partial(jax.jit, static_argnames=(
    "group", "seq_len", "block_q", "block_k", "interpret"))
def flash_dkdv_pallas(q, k, v, do, m, linv, delta, group, seq_len,
                      block_q=512, block_k=512, interpret=None):
    """dk, dv: shapes as in flash_dq_pallas; returns (B*KVH, S, D) pair."""
    BH, S, D = q.shape
    BKV = k.shape[0]
    nq, nk = S // block_q, S // block_k
    n_minor = nq * group
    scale = D**-0.5
    kernel = functools.partial(
        _dkdv_kernel, scale=scale, block_q=block_q, block_k=block_k,
        n_minor=n_minor, group=group, seq_len=seq_len)

    def q_idx(b, j, mi, g=group, nqq=nq):
        return (b * g + mi // nqq, mi % nqq, 0)

    def stat_idx(b, j, mi, g=group, nqq=nq):
        return (b * g + mi // nqq, mi % nqq)

    qspec = pl.BlockSpec((1, block_q, D), q_idx)
    stat = pl.BlockSpec((1, block_q), stat_idx)
    kv = pl.BlockSpec((1, block_k, D), lambda b, j, mi: (b, j, 0))
    out = pl.BlockSpec((1, block_k, D), lambda b, j, mi: (b, j, 0))
    dk, dv = pl.pallas_call(
        kernel,
        grid=(BKV, nk, n_minor),
        in_specs=[qspec, kv, kv, qspec, stat, stat, stat],
        out_specs=[out, out],
        out_shape=[jax.ShapeDtypeStruct((BKV, S, D), k.dtype),
                   jax.ShapeDtypeStruct((BKV, S, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(q, k, v, do, m, linv, delta)
    return dk, dv
