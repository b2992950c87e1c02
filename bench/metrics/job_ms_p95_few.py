"""95th percentile of the wall time of every job completed in the
measured window.  Per layer, not end to end: no cell's window holds the
200 jobs that put ten beyond it."""
from . import percentile


def read(ctx):
    return percentile(ctx.job_s, 95) * 1e3 if ctx.job_s else None
