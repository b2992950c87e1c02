"""Which of the program's named scopes each device op of a trace ran under.

The program names the device work of a secure round with
``jax.named_scope``: ``SCOPES`` below.  A scope is HLO metadata (the
``op_name`` of each instruction), while the device trace names an op
after its HLO instruction (``%fusion.2340 = ...``).  So an op's scope is
read from the instruction of that name in the compiled programs the
process holds (``live_executables`` of JAX's backend), and an op's
innermost scope from its ``op_name``'s path: ``.../summaries/
jit(fused_irls_sim)/f64_terms/mul`` is ``summaries/f64_terms``.

``scope_seconds`` sums a reduced trace's leaf-op device seconds per
innermost scope, with ``""`` for ops under none of them (the scan and
cond plumbing, the objective, the stopping rule, the rng fold, and the
copies XLA inserts, which carry no ``op_name``), so scoped plus unscoped
is the window's whole leaf time.  An op whose instruction no live
program holds, or that several hold under different scopes, is
unmapped: above ``MAX_UNMAPPED`` of the leaf time the split is not
trusted and ``scope_seconds`` gives ``None``, as it does for a program
without these scopes.
"""
from __future__ import annotations

import re
import sys

__all__ = ["SCOPES", "UNSCOPED", "MAX_UNMAPPED", "scope_of", "hlo_index",
           "live_hlo_texts", "scope_seconds", "per_round_ms"]

TOP = ("summaries", "protect", "aggregate", "reveal", "newton_solve")
SUB = ("operands", "gram", "f64_terms")  # inside ``summaries``
SCOPES = TOP[:1] + tuple(f"summaries/{s}" for s in SUB) + TOP[1:]
UNSCOPED = ""
MAX_UNMAPPED = 0.005  # of the window's leaf-op time

_INSTR = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPCODE = re.compile(r" ([\w\-]+)\(")


def scope_of(op_name: str) -> str:
    """The innermost of ``SCOPES`` on an ``op_name`` path, or ``""``."""
    scope = UNSCOPED
    for part in op_name.split("/"):
        if part in TOP:
            scope = part
        elif part in SUB and scope.startswith("summaries"):
            scope = f"summaries/{part}"
    return scope


def hlo_index(texts) -> dict:
    """Instruction name -> [(the text after its ``=``, its scope)] over
    the HLO modules in ``texts``."""
    index: dict = {}
    for text in texts:
        for line in text.splitlines():
            m = _INSTR.match(line)
            if m is None:
                continue
            meta = _OP_NAME.search(line)
            index.setdefault(m.group(1), []).append(
                (m.group(2), scope_of(meta.group(1)) if meta else UNSCOPED))
    return index


def live_hlo_texts() -> list[str]:
    """The HLO text of every compiled program the process holds on the
    default backend."""
    from jax.extend import backend

    return [m.to_string()
            for exe in backend.get_backend().live_executables()
            for m in exe.hlo_modules()]


def _signature(text: str) -> str:
    """An instruction's text up to its opcode: ``f32[4]{0} fusion(``.  The
    trace prints each operand with its shape and the HLO text does not,
    so the two agree up to there."""
    m = _OPCODE.search(text)
    return text[:m.end()] if m else text


def _op_scope(name: str, index: dict) -> str | None:
    """The scope of a trace op named ``%<instruction> = <text>``; ``None``
    where the live programs hold no such instruction or do not tell its
    scope apart."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return None
    found = index.get(head.strip().lstrip("%"), [])
    if len({scope for _, scope in found}) > 1:
        # the same name in several programs: result type and opcode
        # decide (or, where the name was cut short, what is left of it)
        sig = _signature(rest)
        found = [c for c in found
                 if _signature(c[0]) == sig or c[0].startswith(rest)]
    scopes = {scope for _, scope in found}
    return scopes.pop() if len(scopes) == 1 else None


_CACHE: dict = {}


def scope_seconds(summary, texts=None) -> dict | None:
    """Leaf-op device seconds of the traced window per innermost scope
    (``""``: under none), averaged over devices; ``None`` where no op
    ran under any scope or more than ``MAX_UNMAPPED`` of the leaf time
    is unmapped.  ``texts``: the HLO modules that ran (default: the live
    programs)."""
    if texts is None:
        hit = _CACHE.get(id(summary))
        if hit is not None and hit[0] is summary:
            return hit[1]
    index = hlo_index(live_hlo_texts() if texts is None else texts)
    out: dict = {}
    unmapped: dict = {}
    for name, secs in summary.op_s.items():
        scope = _op_scope(name, index)
        into = unmapped if scope is None else out
        key = name if scope is None else scope
        into[key] = into.get(key, 0.0) + secs / summary.devices
    if not any(out.get(s, 0.0) > 0 for s in SCOPES):
        out = None
    else:
        lost = sum(unmapped.values())
        share = lost / (lost + sum(out.values()))
        largest = max(unmapped, key=unmapped.get, default="")
        print(f"scopes: {share:.3%} of the leaf-op time unmapped, "
              f"{len(unmapped)} ops; largest: {largest[:80]!r}",
              file=sys.stderr)
        if share > MAX_UNMAPPED:
            out = None
    if texts is None:
        _CACHE[id(summary)] = (summary, out)
    return out


def per_round_ms(ctx, scopes) -> float | None:
    """Device milliseconds per executed round of the traced window under
    ``scopes`` (each matched as a whole scope or a scope's prefix:
    ``summaries`` holds ``summaries/gram``)."""
    if ctx.trace is None:
        return None
    rounds = sum(a.rounds for a in ctx.traced)
    secs = scope_seconds(ctx.trace)
    if not rounds or secs is None:
        return None
    total = sum(v for k, v in secs.items()
                if any(k == s or (s and k.startswith(s + "/"))
                       for s in scopes))
    return 1e3 * total / rounds
