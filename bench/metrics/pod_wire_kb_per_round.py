"""Kilobytes (10^3 bytes) each chip hands the cross-chip wire per secure
round: the program's counter ``repro_pod_wire_bytes_total`` (raised at
each fit's readback by its executed rounds times the all-reduce operand
of one round, two 16-bit limbs in uint32 per field element) over the
traced jobs, per executed round."""


def read(ctx):
    if not ctx.traced or any(a.wire_bytes is None for a in ctx.traced):
        return None
    rounds = sum(a.rounds for a in ctx.traced)
    if not rounds:
        return None
    return sum(a.wire_bytes for a in ctx.traced) / rounds / 1e3
