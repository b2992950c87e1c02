"""Useful operations of the traced window's jobs over its seconds times
the chip's bfloat16 peak (no float32 or float64 peak is published).

Operations per executed round are ``work.fit_round_flops`` of the
unpadded shapes: the summaries and the solve, nothing recomputed.
"""


def read(ctx):
    if ctx.trace is None or not ctx.traced:
        return None
    flops = sum(ctx.cell.job_flops(a) for a in ctx.traced)
    return 100.0 * flops / (ctx.trace.window_s * ctx.peaks["flops_bf16"])
