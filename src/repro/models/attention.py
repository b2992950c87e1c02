"""Attention: blocked online-softmax (full/windowed), GQA/MQA/MLA, decode.

Never materializes S x S scores: training/prefill attention scans KV blocks
with a running (max, denom, acc) — the flash-attention recurrence in pure
JAX, which is what makes prefill_32k compile inside HBM.  Windowed variants
(SWA / Griffin local) use a *banded* q-block scan whose KV span is constant
(window + one q block), so compiled FLOPs scale with S*window, not S^2.

Full-causal attention pays ~2x ideal FLOPs (masked upper triangle is still
computed) — a known artifact of dense-blocked causal attention; see
EXPERIMENTS.md §Roofline for the accounting and §Perf for the staircase
packing that removes it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


__all__ = ["attend", "decode_attend", "swa_attend_cp"]

NEG_INF = -1e30


def _pick_block(T: int) -> int:
    for cand in (512, 256, 128, 64, 32, 16, 8, 4, 2, 1):
        if T % cand == 0:
            return min(T, cand)
    return T


def _online_block_scan(q, k_span, v_span, q_pos, kv_pos, window, scale,
                       with_stats: bool = False):
    """Online-softmax over KV blocks of a span.

    q: (B, Q, KVH, G, Dk); k_span: (B, T, KVH, Dk); v_span: (B, T, KVH, Dv);
    q_pos: (Q,) absolute positions; kv_pos: (T,) absolute positions
    (entries < 0 are padding and always masked).  Causal + window mask.
    Returns (B, Q, KVH, G, Dv) f32 (unnormalized-then-normalized); with
    ``with_stats`` also the running (m, l) softmax statistics for the
    flash backward.
    """
    B, Q, KVH, G, Dk = q.shape
    T = k_span.shape[1]
    Dv = v_span.shape[-1]
    bk = _pick_block(T)
    nkb = T // bk

    qf = q.astype(jnp.float32) * scale

    def step(carry, j):
        m, l, acc = carry
        ks = jax.lax.dynamic_slice_in_dim(k_span, j * bk, bk, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(v_span, j * bk, bk, axis=1)
        ps = jax.lax.dynamic_slice_in_dim(kv_pos, j * bk, bk, axis=0)
        s = jnp.einsum(
            "bqkgd,btkd->bkgqt", qf, ks.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )  # (B, KVH, G, Q, bk)
        allow = (ps[None, :] <= q_pos[:, None]) & (ps[None, :] >= 0)
        if window:
            allow &= ps[None, :] > (q_pos[:, None] - window)
        s = jnp.where(allow[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum(
            "bkgqt,btkd->bkgqd", p, vs.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KVH, G, Q), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KVH, G, Q), jnp.float32)
    a0 = jnp.zeros((B, KVH, G, Q, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), jnp.arange(nkb), length=nkb
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.transpose(out, (0, 3, 1, 2, 4))  # (B, Q, KVH, G, Dv)
    if with_stats:
        return out, m, l
    return out


# ------------------------------------------------------- flash custom VJP
def _mask(ps, q_pos, window):
    allow = (ps[None, :] <= q_pos[:, None]) & (ps[None, :] >= 0)
    if window:
        allow &= ps[None, :] > (q_pos[:, None] - window)
    return allow


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _flash(q, k_span, v_span, q_pos, kv_pos, window, scale):
    """Online-softmax attention with a flash-style backward.

    Identical forward to _online_block_scan; the custom VJP recomputes
    block scores in the backward instead of letting scan save p / (m, l,
    acc) per KV block — without this, autodiff materializes the full
    S x S score matrix per layer (the dominant §Perf memory bucket for
    dense-train cells).  Residuals: (q, k, v, out, m, l) — O(S·d), not
    O(S^2).
    """
    out, _, _ = _flash_fwd_impl(q, k_span, v_span, q_pos, kv_pos, window,
                                scale)
    return out


def _flash_fwd_impl(q, k_span, v_span, q_pos, kv_pos, window, scale):
    out = _online_block_scan(q, k_span, v_span, q_pos, kv_pos, window,
                             scale, with_stats=True)
    return out


def _flash_fwd(q, k_span, v_span, q_pos, kv_pos, window, scale):
    out, m, l = _flash_fwd_impl(q, k_span, v_span, q_pos, kv_pos, window,
                                scale)
    return out, (q, k_span, v_span, q_pos, kv_pos, out, m, l)


def _flash_bwd(window, scale, res, g):
    q, k_span, v_span, q_pos, kv_pos, out, m, l = res
    B, Q, KVH, G, Dk = q.shape
    T = k_span.shape[1]
    Dv = v_span.shape[-1]
    bk = _pick_block(T)
    nkb = T // bk
    qf = q.astype(jnp.float32) * scale
    g32 = g.astype(jnp.float32)
    # delta_i = sum_d dO_i O_i  (B, KVH, G, Q)
    delta = jnp.einsum("bqkgd,bqkgd->bkgq", g32, out)
    linv = 1.0 / jnp.maximum(l, 1e-30)

    def step(dq_acc, j):
        ks = jax.lax.dynamic_slice_in_dim(k_span, j * bk, bk, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(v_span, j * bk, bk, axis=1)
        ps = jax.lax.dynamic_slice_in_dim(kv_pos, j * bk, bk, axis=0)
        s = jnp.einsum(
            "bqkgd,btkd->bkgqt", qf, ks.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        allow = _mask(ps, q_pos, window)
        s = jnp.where(allow[None, None, None], s, NEG_INF)
        p = jnp.exp(s - m[..., None]) * linv[..., None]  # true probs
        dv_j = jnp.einsum(
            "bkgqt,bqkgd->btkd", p, g32,
            preferred_element_type=jnp.float32,
        )
        dp = jnp.einsum(
            "bqkgd,btkd->bkgqt", g32, vs.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[..., None])  # (B, KVH, G, Q, bk)
        dq_j = jnp.einsum(
            "bkgqt,btkd->bqkgd", ds, ks.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        dk_j = jnp.einsum(
            "bkgqt,bqkgd->btkd", ds, qf,
            preferred_element_type=jnp.float32,
        )
        return dq_acc + dq_j, (dk_j, dv_j)

    dq0 = jnp.zeros((B, Q, KVH, G, Dk), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(step, dq0, jnp.arange(nkb), length=nkb)
    dq = dq * scale
    dk = jnp.moveaxis(dks, 0, 1).reshape(B, T, KVH, Dk)
    dv = jnp.moveaxis(dvs, 0, 1).reshape(B, T, KVH, Dv)
    return (dq.astype(q.dtype), dk.astype(k_span.dtype),
            dv.astype(v_span.dtype), None, None)


_flash.defvjp(_flash_fwd, _flash_bwd)


def attend(q, k, v, *, window: int = 0, q_block: int = 1024,
           q_offset: int = 0, flash_vjp: bool = False):
    """Causal (optionally windowed) attention for train/prefill.

    q: (B, Sq, H, Dk); k: (B, Skv, KVH, Dk); v: (B, Skv, KVH, Dv).
    H % KVH == 0 (GQA); Dv may differ from Dk (MLA).  ``q_offset`` is the
    absolute position of q[0] (0 for train; cache length for chunked
    prefill).  ``flash_vjp`` switches the backward to the flash-style
    recompute (identical forward; see _flash).  Returns (B, Sq, H, Dv) in
    q.dtype.
    """
    B, Sq, H, Dk = q.shape
    Skv, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    scale = Dk**-0.5
    qr = q.reshape(B, Sq, KVH, G, Dk)
    kv_pos = jnp.arange(Skv, dtype=jnp.int32)
    inner = (
        functools.partial(_flash, window=0)
        if flash_vjp else
        functools.partial(_online_block_scan, window=0)
    )

    if not window or window >= Skv:
        # full causal: single q span over all KV blocks
        q_pos = q_offset + jnp.arange(Sq, dtype=jnp.int32)
        out = inner(qr, k, v, q_pos, kv_pos, scale=scale)
        return out.reshape(B, Sq, H, -1).astype(q.dtype)

    # banded: constant KV span per q block = window rounded up + one block
    bq = min(q_block, Sq)
    nqb = Sq // bq
    assert Sq % bq == 0, "pad Sq to q_block"
    span = min(Skv, ((window + bq + bq - 1) // bq) * bq)

    def qstep(_, i):
        q_i = jax.lax.dynamic_slice_in_dim(qr, i * bq, bq, axis=1)
        q_pos = q_offset + i * bq + jnp.arange(bq, dtype=jnp.int32)
        start = jnp.clip(q_offset + (i + 1) * bq - span, 0, Skv - span)
        k_s = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
        v_s = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
        p_s = jax.lax.dynamic_slice_in_dim(kv_pos, start, span, axis=0)
        out_i = _online_block_scan(q_i, k_s, v_s, q_pos, p_s, window, scale)
        return None, out_i

    _, outs = jax.lax.scan(qstep, None, jnp.arange(nqb), length=nqb)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, Sq, KVH, G, -1)
    return out.reshape(B, Sq, H, -1).astype(q.dtype)


def swa_attend_cp(q, k, v, *, window: int, rules, q_block: int = 1024,
                  flash_vjp: bool = False):
    """Context-parallel sliding-window attention (explicit halo exchange).

    S is sharded over the tp axis; each device holds an S/ntp chunk and
    needs only ceil(window / S_local) left-neighbor chunks of K/V — moved
    with ppermute inside shard_map, so the collective cost is the halo
    (window-sized), not per-layer activation all-reduces, and no
    computation is replicated (XLA's auto-partitioner replicates the
    banded q-block scan when left to its own devices — measured 4x flops;
    see EXPERIMENTS.md §Perf h2o prefill iterations).

    Semantics identical to attend(window=...) for S % ntp == 0.
    """
    mesh, tp = rules.mesh, rules.tp_axis
    ntp = rules.tp_size
    B, S, H, Dk = q.shape
    KVH = k.shape[2]
    G = H // KVH
    scale = Dk**-0.5
    S_local = S // ntp
    n_halo = -(-window // S_local)  # ceil: neighbor chunks covering window
    perm = [(i, (i + 1) % ntp) for i in range(ntp)]
    dp = rules.dp_axes
    from jax.sharding import PartitionSpec as P  # local import, tidy deps

    spec = P(dp, tp, None, None)

    def local_fn(q_l, k_l, v_l):
        idx = jax.lax.axis_index(tp)
        halos_k, halos_v = [], []
        kk, vv = k_l, v_l
        for _ in range(n_halo):
            kk = jax.lax.ppermute(kk, tp, perm)
            vv = jax.lax.ppermute(vv, tp, perm)
            halos_k.insert(0, kk)
            halos_v.insert(0, vv)
        k_span = jnp.concatenate(halos_k + [k_l], axis=1)
        v_span = jnp.concatenate(halos_v + [v_l], axis=1)
        start = (idx - n_halo) * S_local
        kv_pos = start + jnp.arange((n_halo + 1) * S_local,
                                    dtype=jnp.int32)
        q_pos = idx * S_local + jnp.arange(S_local, dtype=jnp.int32)
        qr = q_l.reshape(q_l.shape[0], S_local, KVH, G, Dk)
        fn = _flash if flash_vjp else _online_block_scan
        out = fn(qr, k_span, v_span, q_pos, kv_pos, window, scale)
        return out.reshape(q_l.shape[0], S_local, H, -1).astype(q_l.dtype)

    return jax.shard_map(
        local_fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def decode_attend(q, k_cache, v_cache, cache_pos, pos, *, window: int = 0):
    """Single-token decode attention over a (possibly ring) KV cache.

    q: (B, 1, H, Dk); k_cache: (B, T, KVH, Dk); v_cache: (B, T, KVH, Dv);
    cache_pos: (T,) absolute position held in each cache slot (-1 = empty);
    pos: () current absolute position.  Window semantics match attend().
    """
    B, _, H, Dk = q.shape
    T, KVH = k_cache.shape[1], k_cache.shape[2]
    G = H // KVH
    scale = Dk**-0.5
    qf = q.reshape(B, KVH, G, Dk).astype(jnp.float32) * scale
    s = jnp.einsum(
        "bkgd,btkd->bkgt", qf, k_cache.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    allow = (cache_pos <= pos) & (cache_pos >= 0)
    if window:
        allow &= cache_pos > (pos - window)
    s = jnp.where(allow[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bkgt,btkd->bkgd", p, v_cache.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, 1, H, -1).astype(q.dtype)
