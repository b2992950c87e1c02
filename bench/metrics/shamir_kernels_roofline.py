"""Share of their roofline that the Shamir protect and reveal kernels
reach together (``_share_kernel`` in ``kernels/shamir_poly.py`` and the
reconstruct kernel in ``kernels/shamir_reconstruct.py``, whose launches
the device trace names after their wrappers): bytes only, since the
VPU's integer rate is not published.  The least time is the window's
minimum traffic of both (the cell's ``kernel_work``, from
``work.share_kernel`` and ``work.reconstruct_kernel``, one launch each a
round) over HBM bandwidth; the share is that over the two kernels'
summed device time."""
from . import kernel_seconds, roofline_share

SHARE = r"%shamir_encode_share_pallas(\.\d+)? = "
RECONSTRUCT = r"%shamir_reconstruct_pallas(\.\d+)? = "


def read(ctx):
    if ctx.trace is None:
        return None
    share_s = kernel_seconds(ctx.trace, SHARE)
    recon_s = kernel_seconds(ctx.trace, RECONSTRUCT)
    if share_s <= 0 or recon_s <= 0:
        return None
    nbytes = ctx.cell.kernel_work(ctx.traced).get("shamir", 0)
    return roofline_share(ctx.peaks, 0.0, nbytes, share_s + recon_s)
