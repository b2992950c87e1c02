"""Milliseconds of each job's span in which no operation ran on the
device, averaged over the window's jobs: the coordinator's construction,
dispatch, the readback's wait on the host side and the round reports."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.job_host_s:
        return None
    return 1e3 * sum(t.job_host_s) / len(t.job_host_s)
