"""Logical-axis sharding rules -> NamedSharding, with fallback chains.

The production mesh is fixed at (16, 16) ["data", "model"] per pod (plus a
leading "pod" axis multi-pod), but the assigned architectures have head
counts like 40, 24 and 56 that do not divide 16.  Rather than per-arch
meshes, each parameter kind carries a *fallback chain*: e.g. attention QKV
projections are column-parallel over heads when ``H % tp == 0`` and fall
back to row-parallel over d_model (XLA inserts the psum) otherwise.  The
rules are name-based over the parameter pytree paths, MaxText-style.

Institutions (the paper's parties) map to the ``POD_AXIS`` ("pod") axis;
all data-parallel batch axes are ("pod", "data") in multi-pod meshes.
``secure_psum`` runs over ``POD_AXIS`` — it is the axis whose all-reduce
the secret-shared wire replaces — so mesh builders, the secure-psum
benchmark and the SPMD tests all take the name from here.
"""
from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

__all__ = ["MeshRules", "POD_AXIS", "SHARE_AXIS", "make_mesh",
           "param_pspec", "param_shardings"]

# The institution axis: one paper party per pod.  secure_psum's share
# reductions (and the sharded reveal's reduce-scatter) run over this axis.
POD_AXIS = "pod"

# The computation-center axis of the 2D (pod, share) mesh
# (``distributed.multihost``): reveal point j lives on mesh column j, so a
# center-device only ever holds its own share slice and reconstruction is
# a psum of Lagrange-weighted slices over this axis.  Orthogonal to
# POD_AXIS.
SHARE_AXIS = "share"


def make_mesh(axis_shapes, axis_names, devices=None) -> Mesh:
    """``jax.make_mesh`` with Auto axes, so the partitioner places the
    collectives (jax's own default is Explicit axes)."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(AxisType.Auto,) * len(axis_names), devices=devices,
    )


@dataclasses.dataclass(frozen=True)
class MeshRules:
    """Carries the mesh + axis naming; no-ops cleanly when mesh is None."""

    mesh: Mesh | None = None
    tp_axis: str = "model"
    fsdp: bool = True
    pod_axis: str = POD_AXIS

    @property
    def dp_axes(self):
        if self.mesh is None:
            return ("data",)
        return tuple(n for n in self.mesh.axis_names if n != self.tp_axis)

    @property
    def tp_size(self) -> int:
        if self.mesh is None:
            return 1
        return self.mesh.shape[self.tp_axis]

    @property
    def dp_size(self) -> int:
        if self.mesh is None:
            return 1
        s = 1
        for a in self.dp_axes:
            s *= self.mesh.shape[a]
        return s

    def fsdp_axes(self):
        return self.dp_axes if self.fsdp else None

    # activation / intermediate constraints ---------------------------------
    def constrain(self, x, *spec):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*spec))
        )

    def batch_spec(self):
        """Leading-axis data parallelism for activations."""
        return self.dp_axes

    def sharding(self, *spec) -> NamedSharding | None:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, P(*spec))


def _divisible(n: int, by: int) -> bool:
    return by > 0 and n % by == 0


def param_pspec(path: str, shape: tuple[int, ...], rules: MeshRules,
                cfg) -> P:
    """Name-based parameter partition spec with divisibility fallbacks.

    ``path`` is a '/'-joined pytree path; cfg is the ModelConfig (for head
    counts).  Returned specs only ever shard axes that divide evenly.
    """
    tp, fsdp = rules.tp_axis, rules.fsdp_axes()
    tpn = rules.tp_size

    def fs(dim: int):
        """fsdp axes if they divide dim, else None."""
        if fsdp is None:
            return None
        return fsdp if _divisible(dim, rules.dp_size) else None

    name = path.split("/")[-1]
    # ---- FSDP-only (ZeRO-3) mode: block weights row-sharded over the
    # full mesh, no TP.  Activations are batch-sharded over every axis
    # (transformer._block_batch_spec); embed/lm_head keep their usual
    # specs — the head runs in the staged dp-only region.
    if (
        (getattr(cfg, "fsdp_only", False)
         or getattr(cfg, "seq_parallel_prefill", False))
        and len(shape) >= 2
        and name in ("wq", "wk", "wv", "wo", "w1", "w2", "w3",
                     "wq_mla", "wkv_a", "wk_up", "wv_up")
    ):
        full = rules.dp_axes + (tp,) if rules.mesh is not None else None
        sz = rules.dp_size * rules.tp_size
        if full:
            for dim in range(len(shape)):
                if _divisible(shape[dim], sz):
                    spec = [None] * len(shape)
                    spec[dim] = full
                    return P(*spec)
        return P(fs(shape[0]), None)
    # ---- embeddings / unembedding
    if name == "embed":  # (V, d)
        return P(tp if _divisible(shape[0], tpn) else None, fs(shape[1]))
    if name == "lm_head":  # (d, V)
        return P(fs(shape[0]), tp if _divisible(shape[1], tpn) else None)
    # ---- norms / scalars / biases over d
    if name.startswith(("ln", "norm")) or len(shape) <= 1:
        return P(*([None] * len(shape)))
    # ---- attention projections
    if name in ("wq", "wk", "wv", "wkv_b"):  # (d, H*Dh) fused out axis
        heads = {"wq": cfg.num_heads, "wk": cfg.num_kv_heads,
                 "wv": cfg.num_kv_heads, "wkv_b": cfg.num_heads}[name]
        if _divisible(heads, tpn):
            return P(fs(shape[0]), tp)  # column-parallel over heads
        if _divisible(shape[0], tpn):
            return P(tp, None)  # row-parallel fallback (psum after)
        return P(None, None)
    if name == "wo":  # (H*Dh, d)
        if _divisible(cfg.num_heads, tpn):
            return P(tp, fs(shape[1]))  # row-parallel (Megatron pair)
        if _divisible(shape[1], tpn):
            return P(None, tp)
        return P(None, None)
    # ---- MLA projections
    if name in ("wkv_a", "wq_mla"):  # (d, small) down-projections
        return P(fs(shape[0]) if name == "wkv_a" else None, None) \
            if not _divisible(cfg.num_heads, tpn) else P(fs(shape[0]),
                                                         None)
    if name in ("wk_up", "wv_up"):  # (lora, H*dim)
        return P(None, tp if _divisible(cfg.num_heads, tpn) else None)
    # ---- dense MLP
    if name in ("w1", "w3"):  # (d, ff)
        if _divisible(shape[1], tpn):
            return P(fs(shape[0]), tp)
        return P(fs(shape[0]), None)
    if name == "w2":  # (ff, d)
        if _divisible(shape[0], tpn):
            return P(tp, fs(shape[1]))
        return P(None, fs(shape[1]))
    # ---- MoE
    if name == "router":  # (d, E)
        return P(None, None)
    if name.startswith("experts_"):  # (E, d, h) / (E, h, d)
        return P(tp if _divisible(shape[0], tpn) else None, None, None)
    if name.startswith("shared_"):  # shared expert, shard like dense mlp
        if name.endswith(("w1", "w3")):
            return P(fs(shape[0]),
                     tp if _divisible(shape[1], tpn) else None)
        return P(tp if _divisible(shape[0], tpn) else None, fs(shape[1]))
    # ---- RWKV6 (heads rarely divide tp)
    if name.startswith("rwkv_w_"):  # (d, d) / channel-mix projections
        if getattr(cfg, "rwkv_batch_parallel", False):
            # batch-parallel mode: weights FSDP-sharded over the FULL mesh,
            # no TP — activations are batch-sharded over (data x model)
            # instead (see transformer._apply_block), so no per-projection
            # psums; full-mesh sharding keeps the backward's gradient
            # accumulators sharded too (they follow the param spec).
            full = rules.dp_axes + (tp,) if rules.mesh is not None else None
            sz = rules.dp_size * rules.tp_size
            if full and _divisible(shape[0], sz):
                return P(full, None)
            return P(fs(shape[0]), None)
        if _divisible(shape[0], tpn):
            return P(tp, None)  # row-parallel (psum after)
        return P(None, None)
    # ---- RG-LRU / Griffin
    if name in ("lru_in", "lru_gate"):  # (d, lru)
        return P(fs(shape[0]), tp if _divisible(shape[1], tpn) else None)
    if name == "lru_out":  # (lru, d)
        return P(tp if _divisible(shape[0], tpn) else None, fs(shape[1]))
    if name.startswith("lru_"):  # per-channel vectors (lru,)
        return P(*([None] * len(shape)))
    # default: replicate
    return P(*([None] * len(shape)))


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_shardings(params_shape, rules: MeshRules, cfg):
    """Map a (possibly abstract) param pytree to NamedShardings."""
    def one(path, leaf):
        pstr = _path_str(path)
        if "segments" in pstr and leaf.ndim >= 1:
            # stacked-over-layers leaf: (L_seg, *unstacked); the scan axis
            # stays unsharded, rules apply to the per-layer shape.
            spec = param_pspec(pstr, leaf.shape[1:], rules, cfg)
            return rules.sharding(None, *spec)
        spec = param_pspec(pstr, leaf.shape, rules, cfg)
        return rules.sharding(*spec)

    return jax.tree_util.tree_map_with_path(one, params_shape)
