"""Jaxpr taint verifier: SECRET may only reach PUBLIC through a reveal.

The pass walks a driver round's closed jaxpr (``jax.make_jaxpr`` output)
with a four-level taint lattice, join = max:

* ``PUBLIC`` (0)        — revealed aggregates, beta, lambda, rng keys.
* ``PROTECTED_AGG`` (1) — the share buffer of the *aggregated* secret
  (Algorithm 2 has run over an institution/pod axis of size >= 2).
  Structurally still shares, but the underlying secret is the global
  sum — the only thing a reveal may reconstruct.
* ``PROTECTED`` (2)     — per-institution Shamir share buffers straight
  out of the encode+share kernel.  Revealing these reconstructs ONE
  institution's summary: a violation.
* ``SECRET`` (3)        — institution-local inputs (X, y, counts, fold
  ids) and anything derived from them before protection.

Transitions the verifier recognizes (everything else joins its inputs):

* ``jit(_protect_flat)`` — the fused fixed-point-encode + Horner share
  kernel: outputs are PROTECTED whatever came in (SECRET -> PROTECTED).
* ``reduce_sum`` over the institution axis of a batched share buffer
  (axis ndim-3 of a >=5D PROTECTED operand — the (w, R, [C,] S, rows,
  128) layout — with size >= 2): Algorithm 2, PROTECTED ->
  PROTECTED_AGG.  A reduction over any *other* axis of a share buffer
  (rows, lanes, residues) does NOT aggregate institutions and keeps the
  taint, so slicing tricks cannot launder a single contribution.
* ``psum`` / ``reduce_scatter`` over a mesh axis of size >= 2 on a
  PROTECTED operand: the SPMD form of Algorithm 2 -> PROTECTED_AGG.
  A SECRET operand is a violation: institution data would cross the
  wire between devices in the clear.  Every such collective's operand
  taint is listed in the report's ``wire_operands``.
* ``jit(_reveal_flat)`` — the fused Lagrange+CRT reconstruction: the
  ONLY declassification of share material.  Requires (a) input taint
  exactly PROTECTED_AGG (SECRET means protect was skipped; PROTECTED
  means a per-institution buffer is being revealed) and (b) a
  threshold-satisfying share axis (leading dim >= t).  Outputs PUBLIC.
* ``jit(_distributed_reveal)`` — the 2D-mesh collective reveal: same
  contract, with the share *mesh axis* (its size must be >= t) standing
  in for the stacked share dim.
* ``jit(declassify_sum)`` — the sanctioned *plaintext* aggregation
  annotation (``core.secure_agg.declassify_sum``) used by the
  ``protect != "both"`` modes the paper allows: requires an actually
  aggregating reduction (>= 2 addends); SECRET -> PUBLIC with the site
  recorded in the report's declassification audit trail.

Violations: SECRET/PROTECTED reaching a host callback
(``debug_callback`` / ``io_callback`` / ``pure_callback``) or any
jaxpr output (outputs feed RoundReport telemetry and downstream hosts).
Sub-jaxprs of jit/scan/cond/while/shard_map are walked recursively
(scan and while to a carry fixpoint); shard_map pushes its mesh's axis
sizes so collective rules know whether an axis actually aggregates.
"""
from __future__ import annotations

import dataclasses
import math

from jax.extend import core as jax_core

from .report import AnalysisReport, Finding

__all__ = [
    "PUBLIC",
    "PROTECTED_AGG",
    "PROTECTED",
    "SECRET",
    "TAINT_NAMES",
    "verify_jaxpr",
    "iter_eqns",
]

PUBLIC, PROTECTED_AGG, PROTECTED, SECRET = 0, 1, 2, 3
TAINT_NAMES = {
    PUBLIC: "PUBLIC",
    PROTECTED_AGG: "PROTECTED_AGG",
    PROTECTED: "PROTECTED",
    SECRET: "SECRET",
}

# host-callback primitives: taint > PUBLIC crossing one is a leak (the
# callback's payload materializes on the host outside the protocol)
CALLBACK_PRIMS = {"debug_callback", "io_callback", "pure_callback"}

# collective primitives that sum over a mesh axis (Algorithm 2 on the
# wire when applied to a share buffer)
_SUM_COLLECTIVES = {"psum", "reduce_scatter", "psum_scatter"}


@dataclasses.dataclass
class _Ctx:
    """Walk state threaded through sub-jaxpr recursion."""

    threshold: int
    axis_sizes: dict
    report: AnalysisReport
    mute: int = 0  # >0 during fixpoint warm-up passes (findings suppressed)

    def add(self, severity, where, message):
        if not self.mute:
            self.report.add(Finding("taint", severity, where, message))

    def wired(self, where, taint):
        if not self.mute:
            entry = f"{where}: {TAINT_NAMES[taint]}"
            if entry not in self.report.wire_operands:
                self.report.wire_operands.append(entry)

    def declassified(self, where, what):
        if not self.mute:
            entry = f"{where}: {what}"
            if entry not in self.report.declassifications:
                self.report.declassifications.append(entry)


def _join(taints):
    return max(taints, default=PUBLIC)


def _read(env, atom):
    if isinstance(atom, jax_core.Literal):
        return PUBLIC
    return env.get(atom, PUBLIC)


def _eqn_label(eqn) -> str:
    name = eqn.primitive.name
    inner = eqn.params.get("name")
    return f"{name}({inner})" if inner else name


def _sub_jaxpr(val):
    """Normalize ClosedJaxpr/Jaxpr params to (jaxpr, has_consts)."""
    if hasattr(val, "jaxpr"):
        return val.jaxpr
    return val


def _prod(shape):
    return math.prod(shape) if shape else 1


# -- declassifier / transition rules for named jit calls -----------------


def _rule_protect_flat(eqn, ins, ctx, where):
    return [PROTECTED] * len(eqn.outvars)


def _share_buf_invar(eqn):
    """The share-buffer operand: the highest-rank uint32 input."""
    best = None
    for v in eqn.invars:
        aval = getattr(v, "aval", None)
        if aval is None or not hasattr(aval, "shape"):
            continue
        if best is None or len(aval.shape) > len(best.aval.shape):
            best = v
    return best


def _rule_reveal_flat(eqn, ins, ctx, where):
    buf = _share_buf_invar(eqn)
    t = ctx.threshold
    if buf is not None and len(buf.aval.shape) >= 1:
        k = buf.aval.shape[0]
        if k < t:
            ctx.add(
                "error", where,
                f"reveal from {k} share slices < threshold t={t}: "
                "below-threshold reconstruction",
            )
    taint = _join(ins)
    if taint == SECRET:
        ctx.add(
            "error", where,
            "reveal of UNPROTECTED institution-local data (the operand "
            "never went through the encode+share kernel)",
        )
    elif taint == PROTECTED:
        ctx.add(
            "error", where,
            "reveal of a PER-INSTITUTION share buffer: Algorithm 2 "
            "(the institution-axis aggregation) never ran, so this "
            "reconstructs a single institution's summary",
        )
    else:
        ctx.declassified(
            where,
            "threshold Lagrange reveal of the aggregated share buffer",
        )
    return [PUBLIC] * len(eqn.outvars)


def _rule_distributed_reveal(eqn, ins, ctx, where):
    from ..distributed.sharding import SHARE_AXIS

    t = ctx.threshold
    share_sz = ctx.axis_sizes.get(SHARE_AXIS)
    if share_sz is None:
        ctx.add(
            "warning", where,
            f"distributed reveal outside a mesh with a '{SHARE_AXIS}' "
            "axis: cannot prove the center count >= t",
        )
    elif share_sz < t:
        ctx.add(
            "error", where,
            f"distributed reveal over a share axis of {share_sz} "
            f"centers < threshold t={t}",
        )
    taint = _join(ins)
    if taint == SECRET:
        ctx.add(
            "error", where,
            "distributed reveal of UNPROTECTED institution-local data",
        )
    elif taint == PROTECTED:
        ctx.add(
            "error", where,
            "distributed reveal of a PER-INSTITUTION share slice "
            "(pod-axis aggregation never ran)",
        )
    else:
        ctx.declassified(
            where, "distributed (share-axis collective) Lagrange reveal"
        )
    return [PUBLIC] * len(eqn.outvars)


def _rule_declassify_sum(eqn, ins, ctx, where):
    taint = _join(ins)
    in_elems = max(
        (_prod(v.aval.shape) for v in eqn.invars
         if hasattr(getattr(v, "aval", None), "shape")),
        default=1,
    )
    out_elems = max(
        (_prod(v.aval.shape) for v in eqn.outvars
         if hasattr(getattr(v, "aval", None), "shape")),
        default=1,
    )
    if taint in (PROTECTED, PROTECTED_AGG):
        ctx.add(
            "error", where,
            "declassify_sum applied to SHARE material — shares must go "
            "through the threshold reveal, never a plaintext sum",
        )
    elif in_elems < 2 * max(out_elems, 1):
        ctx.add(
            "error", where,
            f"declassify_sum does not aggregate ({in_elems} -> "
            f"{out_elems} elements): a non-reducing 'sum' would "
            "declassify an individual contribution",
        )
    elif taint == SECRET:
        ctx.declassified(
            where,
            "annotated plaintext aggregation over the institution axis "
            f"({in_elems // max(out_elems, 1)} addends)",
        )
    return [PUBLIC] * len(eqn.outvars)


_PJIT_RULES = {
    "_protect_flat": _rule_protect_flat,
    "_reveal_flat": _rule_reveal_flat,
    "_distributed_reveal": _rule_distributed_reveal,
    "declassify_sum": _rule_declassify_sum,
}


# -- structural recursion --------------------------------------------------


def _eval_jaxpr(jaxpr, in_taints, ctx, path):
    env = {}
    for v, t in zip(jaxpr.invars, in_taints):
        env[v] = t
    for v in jaxpr.constvars:
        env[v] = PUBLIC  # trace-time constants (keys, static tables)
    for i, eqn in enumerate(jaxpr.eqns):
        where = f"{path}/eqn[{i}]:{_eqn_label(eqn)}"
        ins = [_read(env, a) for a in eqn.invars]
        outs = _eval_eqn(eqn, ins, ctx, where)
        # dropped outputs are never read, so binding them is harmless
        env.update(zip(eqn.outvars, outs))
    return [_read(env, a) for a in jaxpr.outvars]


def _fixpoint_body(body_jaxpr, consts, carry, xs, ctx, path,
                   num_carry: int, max_iters: int = 8):
    """Carry-taint fixpoint for scan/while bodies.

    Warm-up passes run muted (findings would duplicate per iteration);
    one final unmuted pass at the fixed carry taints collects findings.
    """
    carry_t = list(carry)
    ctx.mute += 1
    try:
        for _ in range(max_iters):
            outs = _eval_jaxpr(body_jaxpr, consts + carry_t + xs, ctx,
                               path)
            new_carry = [max(a, b)
                         for a, b in zip(carry_t, outs[:num_carry])]
            if new_carry == carry_t:
                break
            carry_t = new_carry
    finally:
        ctx.mute -= 1
    outs = _eval_jaxpr(body_jaxpr, consts + carry_t + xs, ctx, path)
    return carry_t, outs


def _eval_eqn(eqn, ins, ctx, where):
    prim = eqn.primitive.name
    params = eqn.params

    if prim in CALLBACK_PRIMS:
        taint = _join(ins)
        if taint > PUBLIC:
            ctx.add(
                "error", where,
                f"{TAINT_NAMES[taint]} data reaches host callback "
                f"'{prim}': callback payloads leave the protocol "
                "(logs, telemetry, debuggers)",
            )
        return [PUBLIC] * len(eqn.outvars)

    if prim == "jit":
        name = params.get("name", "")
        rule = _PJIT_RULES.get(name)
        if rule is not None:
            return rule(eqn, ins, ctx, where)
        sub = _sub_jaxpr(params["jaxpr"])
        return _eval_jaxpr(sub, ins, ctx, where)

    if prim == "closed_call" or prim == "core_call":
        sub = _sub_jaxpr(params["call_jaxpr"])
        return _eval_jaxpr(sub, ins, ctx, where)

    if prim == "scan":
        sub = _sub_jaxpr(params["jaxpr"])
        nc, ncar = params["num_consts"], params["num_carry"]
        consts, carry, xs = ins[:nc], ins[nc:nc + ncar], ins[nc + ncar:]
        carry_t, outs = _fixpoint_body(
            sub, consts, carry, xs, ctx, where, ncar
        )
        return carry_t + outs[ncar:]

    if prim == "while":
        cond_sub = _sub_jaxpr(params["cond_jaxpr"])
        body_sub = _sub_jaxpr(params["body_jaxpr"])
        cn, bn = params["cond_nconsts"], params["body_nconsts"]
        cond_consts = ins[:cn]
        body_consts = ins[cn:cn + bn]
        carry = ins[cn + bn:]
        carry_t, _ = _fixpoint_body(
            body_sub, body_consts, carry, [], ctx, where, len(carry)
        )
        _eval_jaxpr(cond_sub, cond_consts + carry_t, ctx,
                    f"{where}/cond")
        return carry_t

    if prim == "cond":
        branches = params["branches"]
        ops = ins[1:]
        branch_outs = [
            _eval_jaxpr(_sub_jaxpr(b), ops, ctx, f"{where}/branch{i}")
            for i, b in enumerate(branches)
        ]
        return [max(ts) for ts in zip(*branch_outs)]

    if prim == "shard_map":
        sub = _sub_jaxpr(params["jaxpr"])
        mesh = params.get("mesh")
        saved = ctx.axis_sizes
        if mesh is not None and hasattr(mesh, "shape"):
            ctx.axis_sizes = {**saved, **dict(mesh.shape)}
        try:
            return _eval_jaxpr(sub, ins, ctx, where)
        finally:
            ctx.axis_sizes = saved

    if prim == "reduce_sum":
        taint = _join(ins)
        if taint == PROTECTED:
            aval = eqn.invars[0].aval
            axes = tuple(params.get("axes", ()))
            ndim = len(aval.shape)
            # the batched share layout is (w, R, [C,] S, rows, lanes):
            # the institution axis sits at ndim-3 in every variant, and
            # ONLY a reduction there is Algorithm 2
            if (ndim >= 5 and axes == (ndim - 3,)
                    and aval.shape[ndim - 3] >= 2):
                return [PROTECTED_AGG] * len(eqn.outvars)
        return [taint] * len(eqn.outvars)

    if prim in _SUM_COLLECTIVES:
        taint = _join(ins)
        ctx.wired(where, taint)
        if taint == SECRET:
            ctx.add(
                "error", where,
                f"SECRET institution-local data summed over a mesh axis "
                f"by '{prim}': only share buffers may cross the wire "
                "between devices",
            )
        elif taint == PROTECTED:
            size = _collective_axis_size(params, ctx)
            if size is None:
                ctx.add(
                    "warning", where,
                    f"'{prim}' over a mesh axis of unknown size on a "
                    "share buffer: cannot prove it aggregates >= 2 "
                    "institutions",
                )
                return [PROTECTED] * len(eqn.outvars)
            if size >= 2:
                return [PROTECTED_AGG] * len(eqn.outvars)
            return [PROTECTED] * len(eqn.outvars)
        return [taint] * len(eqn.outvars)

    # default: outputs join the inputs (sound for every elementwise /
    # structural primitive; opaque calls — pallas_call, custom_jvp,
    # linear solves — conservatively propagate their strongest input)
    return [_join(ins)] * len(eqn.outvars)


def _collective_axis_size(params, ctx):
    """Total size of a sum-collective's named axes, if statically known."""
    names = params.get("axes", params.get("axis_name", ()))
    if not isinstance(names, (tuple, list)):
        names = (names,)
    named = [n for n in names if isinstance(n, str)]
    if "axis_size" in params and params["axis_size"] is not None:
        return params["axis_size"]
    if not named:
        return None
    total = 1
    for n in named:
        sz = ctx.axis_sizes.get(n)
        if sz is None:
            return None
        total *= sz
    return total


# -- entry points ----------------------------------------------------------


def verify_jaxpr(closed_jaxpr, in_taints, threshold: int,
                 axis_sizes: dict | None = None,
                 target: str = "jaxpr",
                 report: AnalysisReport | None = None) -> AnalysisReport:
    """Run the taint pass over one closed jaxpr.

    ``in_taints`` aligns 1:1 with the jaxpr's flat invars (use
    ``jax.tree_util.tree_leaves`` on a taint pytree shaped like the
    traced function's arguments).  Outputs carrying taint above PUBLIC
    are violations: driver outputs feed RoundReport telemetry, host
    convergence checks, and checkpoint files.
    """
    jaxpr = closed_jaxpr.jaxpr
    if len(in_taints) != len(jaxpr.invars):
        raise ValueError(
            f"{target}: got {len(in_taints)} taints for "
            f"{len(jaxpr.invars)} jaxpr inputs"
        )
    rep = report or AnalysisReport(target=target)
    ctx = _Ctx(threshold=threshold, axis_sizes=dict(axis_sizes or {}),
               report=rep)
    out_taints = _eval_jaxpr(jaxpr, list(in_taints), ctx, target)
    for i, t in enumerate(out_taints):
        if t == SECRET:
            rep.add(Finding(
                "taint", "error", f"{target}/outvars[{i}]",
                "output carries SECRET taint: institution-local data "
                "reaches a revealed/telemetry output",
            ))
        elif t in (PROTECTED, PROTECTED_AGG):
            rep.add(Finding(
                "taint", "error", f"{target}/outvars[{i}]",
                f"output carries {TAINT_NAMES[t]} share material: "
                "share buffers must never leave the round graph",
            ))
    if not rep.declassifications and any(
        t == SECRET for t in in_taints
    ) and rep.ok:
        rep.add(Finding(
            "taint", "warning", target,
            "SECRET inputs but no declassification site found: the "
            "graph never reveals (vacuously safe — check the spec)",
        ))
    return rep


def iter_eqns(jaxpr, path: str = "", axis_sizes: dict | None = None):
    """Yield ``(path, eqn, axis_sizes)`` over a jaxpr and all sub-jaxprs.

    Structural walk used by the lint passes (mesh-axis checks, callback
    census).  ``axis_sizes`` carries the innermost enclosing shard_map
    mesh's axis sizes at each yield point.
    """
    sizes = dict(axis_sizes or {})
    jaxpr = _sub_jaxpr(jaxpr)
    for i, eqn in enumerate(jaxpr.eqns):
        where = f"{path}/eqn[{i}]:{_eqn_label(eqn)}"
        yield where, eqn, sizes
        inner_sizes = sizes
        if eqn.primitive.name == "shard_map":
            mesh = eqn.params.get("mesh")
            if mesh is not None and hasattr(mesh, "shape"):
                inner_sizes = {**sizes, **dict(mesh.shape)}
        for key in ("jaxpr", "call_jaxpr", "cond_jaxpr", "body_jaxpr"):
            if key in eqn.params:
                sub = eqn.params[key]
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    yield from iter_eqns(sub, where, inner_sizes)
        for bi, b in enumerate(eqn.params.get("branches", ())):
            yield from iter_eqns(b, f"{where}/branch{bi}", inner_sizes)
