"""Pallas TPU kernel: causal flash attention forward (LM-side hot spot).

The §Perf analysis (EXPERIMENTS.md) shows the remaining memory-bound bytes
in dense train/prefill cells are the per-block score tensors the XLA-level
flash path still materializes to HBM ((B, KVH, G, Q, bk) f32 fusions).
This kernel keeps them VMEM-resident: one (block_q, D) query tile is held
against streamed (block_k, D) K/V tiles; scores, the online-softmax state
(m, l) and the output accumulator never touch HBM.  HBM traffic becomes
exactly q + k + v + o — the flash-attention bound.

Mapping:
  grid = (B*H, S/block_q, S/block_k); the k axis is the minor (sequential)
  grid dimension, so VMEM scratch (m, l, acc) persists across it — the
  standard Pallas flash pattern.  GQA is handled in the index map: query
  head h reads KV head h // group from the (B*KVH, S, D) K/V arrays — no
  broadcast copies in HBM.  Causal masking uses global positions; fully
  masked (future) K blocks are skipped with pl.when.

VMEM budget at (block_q, block_k, D) = (512, 512, 128), f32 accumulators:
q 256 KB + k/v 2x128 KB (bf16) + acc 256 KB + scores 1 MB  ~<2 MB, well
inside ~16 MB with double buffering.

ops.flash_attention is the jit'd wrapper (padding, GQA reshape);
ref.flash_attention is the pure-jnp oracle; validated in interpret mode
across shapes/dtypes in tests/test_kernels_flash.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret

__all__ = ["flash_attention_pallas"]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, m_scr, l_scr,
            acc_scr, *, scale, block_q, block_k, nk, seq_len):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )

    # skip fully-future K blocks (strictly above the causal diagonal)
    @pl.when(ki * block_k <= qi * block_q + block_q - 1)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale     # (bq, D)
        k = k_ref[0].astype(jnp.float32)             # (bk, D)
        v = v_ref[0].astype(jnp.float32)             # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                            # (bq, bk)
        mask = (k_pos <= q_pos) & (k_pos < seq_len)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                       # (bq, bk)
        corr = jnp.exp(m_prev - m_new)               # (bq, 1)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)
        m_ref[0] = m_scr[...][:, 0]
        l_ref[0] = l_scr[...][:, 0]


@functools.partial(
    jax.jit,
    static_argnames=("group", "block_q", "block_k", "seq_len", "interpret"),
)
def flash_attention_pallas(
    q: jnp.ndarray,   # (B*H, S_pad, D)
    k: jnp.ndarray,   # (B*KVH, S_pad, D)
    v: jnp.ndarray,   # (B*KVH, S_pad, D)
    group: int,       # H // KVH
    seq_len: int,     # true (unpadded) length, for masking
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Causal flash attention -> (o, m, l).  Caller pads S to a block
    multiple and D to 128 (ops.py).  ``interpret=None`` follows the
    backend (``kernels.backend``); the (m, l) softmax statistics feed
    the backward kernels."""
    BH, S, D = q.shape
    assert S % block_q == 0 and S % block_k == 0, "caller pads S"
    nq, nk = S // block_q, S // block_k
    scale = D**-0.5
    kernel = functools.partial(
        _kernel, scale=scale, block_q=block_q, block_k=block_k, nk=nk,
        seq_len=seq_len,
    )
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda b, i, j, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda b, i, j, g=group: (b // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q), lambda b, i, j: (b, i)),
            pl.BlockSpec((1, block_q), lambda b, i, j: (b, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, S), jnp.float32),
            jax.ShapeDtypeStruct((BH, S), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # m (running max)
            pltpu.VMEM((block_q, 1), jnp.float32),   # l (running denom)
            pltpu.VMEM((block_q, D), jnp.float32),   # acc
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
