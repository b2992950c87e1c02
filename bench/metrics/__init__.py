"""Metric readers, one module per metric, found by the metric's name.

Each module defines ``read(ctx)``, which returns the metric's value from
the run's record (``run.Context``) or ``None`` where the run holds
nothing to read it from.
"""
from __future__ import annotations

import re

__all__ = ["kernel_seconds", "roofline_share", "percentile"]


def kernel_seconds(summary, pattern: str) -> float:
    """Summed device seconds of the operations whose name matches
    ``pattern`` (a regular expression, matched from the start)."""
    rx = re.compile(pattern)
    return sum(v for k, v in summary.op_s.items() if rx.match(k))


def roofline_share(peaks: dict, flops: float, nbytes: float,
                   seconds: float):
    """100 x the least time for ``flops`` and ``nbytes`` (the larger of
    operations over the bfloat16 peak and bytes over HBM bandwidth) over
    the ``seconds`` the kernel took; ``None`` with nothing to read."""
    if seconds <= 0 or (flops <= 0 and nbytes <= 0):
        return None
    least = max(flops / peaks["flops_bf16"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def percentile(values, q: float) -> float:
    """The ``q``-th percentile, linear between the closest ranks."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
