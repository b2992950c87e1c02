"""Token-choice top-k MoE with expert parallelism over the 'model' axis.

Scheme: *replicated dispatch* EP.  Activations are data-sharded and
model-replicated under our pjit layout, so every model shard already holds
all tokens of its data shard.  Each shard therefore:

  1. routes its local tokens (router is replicated),
  2. builds a capacity-bounded (E_local, C, d) dispatch buffer for the
     experts *it owns* only (scatter with drop),
  3. runs its expert FFNs,
  4. scatters results back to token order weighted by router gates,
  5. psum over the 'model' axis merges the k expert contributions that live
     on different shards (this all-reduce is the only EP collective, the
     same cost as a Megatron TP all-reduce).

Steps 2-5 run inside shard_map (manual collectives); everything composes
with the auto-sharded pjit program around it.  Tokens beyond capacity
C = ceil(T k cf / E) are dropped (standard Switch-style; drop counts are
returned for monitoring).  Shared experts (DeepSeek-style) are computed
TP-style inside the same shard_map and merged into the same psum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["moe_ffn", "router_aux_loss"]


def _route(x, router_w, top_k):
    """x: (T, d) -> (gates (T,k) f32, experts (T,k) i32, probs (T,E) f32)."""
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, experts, probs


def router_aux_loss(probs, experts, num_experts):
    """Switch-style load-balancing loss: E * sum_e f_e * P_e."""
    T = probs.shape[0]
    counts = jnp.zeros((num_experts,), jnp.float32).at[
        experts.reshape(-1)
    ].add(1.0)
    f = counts / jnp.maximum(counts.sum(), 1.0)
    p_mean = probs.mean(axis=0)
    return num_experts * jnp.sum(f * p_mean)


def _local_expert_pass(x, gates, experts, w1, w3, w2, capacity, e_offset,
                       num_experts):
    """Dispatch local tokens to locally-owned experts, compute, combine.

    x: (T, d); gates/experts: (T, k); w*: (E_loc, ...); returns (T, d)
    partial output (zero rows for tokens whose experts live elsewhere) and
    the number of dropped assignments.
    """
    T, d = x.shape
    k = experts.shape[1]
    E_loc = w1.shape[0]
    fe = experts.reshape(-1)  # (T*k,) global expert ids
    gate_flat = gates.reshape(-1)
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)

    # position of each assignment within its expert queue (over ALL experts
    # so ordering is shard-invariant), via sort-based ranking
    order = jnp.argsort(fe, stable=True)
    fe_sorted = fe[order]
    # start offset of each expert's run
    seg_start = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(jnp.bincount(fe_sorted, length=num_experts), axis=0)[
             :-1
         ].astype(jnp.int32)]
    )
    pos_sorted = jnp.arange(T * k, dtype=jnp.int32) - seg_start[fe_sorted]
    pos = jnp.zeros((T * k,), jnp.int32).at[order].set(pos_sorted)

    local = (fe >= e_offset) & (fe < e_offset + E_loc)
    kept = local & (pos < capacity)
    dropped = jnp.sum(local & (pos >= capacity))
    slot = jnp.where(kept, (fe - e_offset) * capacity + pos, E_loc * capacity)
    # scatter token *ids* (int32), then gather x straight into the
    # capacity buffer — scattering x[tok] directly would materialize a
    # (T*k, d) copy of the activations (k x the activation bytes; the
    # dominant §Perf memory bucket for the MoE train cells).
    tok_buf = jnp.full((E_loc * capacity + 1,), T, jnp.int32)
    tok_buf = tok_buf.at[slot].set(tok, mode="drop")[:-1]
    x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)
    buf = x_pad[tok_buf].reshape(E_loc, capacity, d)

    h = jnp.einsum("ecd,edh->ech", buf, w1)
    g = jax.nn.silu(jnp.einsum("ecd,edh->ech", buf, w3))
    out_buf = jnp.einsum("ech,ehd->ecd", h * g, w2)  # (E_loc, C, d)

    # combine: per-token gather of its k expert rows, weighted reduce over
    # k in one fusion (the gather is a fusable producer — no (T*k, d)
    # intermediate in HBM).  Dropped/remote assignments point at a zero row.
    flat_out = jnp.concatenate(
        [out_buf.reshape(E_loc * capacity, d),
         jnp.zeros((1, d), out_buf.dtype)], axis=0,
    )
    slot_2d = jnp.where(kept, slot, E_loc * capacity).reshape(T, k)
    w_2d = jnp.where(kept, gate_flat, 0.0).reshape(T, k).astype(x.dtype)
    y = jnp.einsum("tkd,tk->td", flat_out[slot_2d], w_2d)
    return y, dropped


def moe_ffn(x, params, cfg, rules):
    """MoE FFN.  x: (B, S, d) global (pjit-sharded).  Returns (y, aux).

    params: router (d, E); experts_w1/w3 (E, d, h); experts_w2 (E, h, d);
    optional shared_w1/w3 (d, hs), shared_w2 (hs, d).
    """
    B, S, d = x.shape
    E, k = cfg.moe_num_experts, cfg.moe_top_k
    mesh = rules.mesh
    tp = rules.tp_axis
    dp = rules.dp_axes

    def inner(x_loc, router_w, w1, w3, w2, *shared):
        Bl, Sl, _ = x_loc.shape
        T = Bl * Sl
        xt = x_loc.reshape(T, d)
        gates, experts, probs = _route(xt, router_w, k)
        aux = router_aux_loss(probs, experts, E)
        capacity = max(1, int(T * k * cfg.capacity_factor / E))
        E_loc = w1.shape[0]
        tp_index = jax.lax.axis_index(tp)
        e_offset = (tp_index * E_loc).astype(jnp.int32)
        y, dropped = _local_expert_pass(
            xt, gates, experts, w1, w3, w2, capacity, e_offset, E
        )
        if shared:
            sw1, sw3, sw2 = shared
            h = jnp.einsum("td,dh->th", xt, sw1)
            g = jax.nn.silu(jnp.einsum("td,dh->th", xt, sw3))
            y = y + jnp.einsum("th,hd->td", h * g, sw2)
        # one all-reduce merges expert contributions + shared TP partials
        y = jax.lax.psum(y, tp)
        aux = jax.lax.pmean(aux, tp)
        drop_frac = dropped.astype(jnp.float32) / (T * k)
        return (y.reshape(Bl, Sl, d), aux,
                jax.lax.pmax(drop_frac, tp))

    if mesh is None:
        # single-host fallback: one shard holding all experts
        def inner_local(x_loc, router_w, w1, w3, w2, *shared):
            Bl, Sl, _ = x_loc.shape
            T = Bl * Sl
            xt = x_loc.reshape(T, d)
            gates, experts, probs = _route(xt, router_w, k)
            aux = router_aux_loss(probs, experts, E)
            capacity = max(1, int(T * k * cfg.capacity_factor / E))
            y, dropped = _local_expert_pass(
                xt, gates, experts, w1, w3, w2, capacity, jnp.int32(0), E
            )
            if shared:
                sw1, sw3, sw2 = shared
                h = jnp.einsum("td,dh->th", xt, sw1)
                g = jax.nn.silu(jnp.einsum("td,dh->th", xt, sw3))
                y = y + jnp.einsum("th,hd->td", h * g, sw2)
            return (y.reshape(Bl, Sl, d), aux,
                    dropped.astype(jnp.float32) / (T * k))

        args = [x, params["router"], params["experts_w1"],
                params["experts_w3"], params["experts_w2"]]
        if "shared_w1" in params:
            args += [params["shared_w1"], params["shared_w3"],
                     params["shared_w2"]]
        return inner_local(*args)

    assert E % rules.tp_size == 0, "expert count must divide the model axis"
    e_spec = P(tp, None, None)
    in_specs = [P(dp, None, None), P(None, None), e_spec, e_spec, e_spec]
    args = [x, params["router"], params["experts_w1"], params["experts_w3"],
            params["experts_w2"]]
    if "shared_w1" in params:
        hs_ok = params["shared_w1"].shape[1] % rules.tp_size == 0
        s_col = P(None, tp) if hs_ok else P(None, None)
        s_row = P(tp, None) if hs_ok else P(None, None)
        in_specs += [s_col, s_col, s_row]
        args += [params["shared_w1"], params["shared_w3"],
                 params["shared_w2"]]
    out_specs = (P(dp, None, None), P(), P())
    fn = jax.shard_map(
        inner, mesh=mesh, in_specs=tuple(in_specs), out_specs=out_specs,
        check_vma=False,
    )
    return fn(*args)
