"""Share of its roofline that the fused summaries kernel reaches
(``_irls_kernel`` in ``kernels/fused_irls.py``, whose launch the device
trace names after its wrapper, ``fused_irls_pallas``): the least time the
chip could take for the traced window's launches, the larger of
operations over the bfloat16 peak and bytes over HBM bandwidth (the
cell's ``kernel_work``, from ``work.irls_kernel``: its own inputs read
once, outputs written once), over the kernel's summed device time.  At
d = 128 the bytes bound it."""
from . import kernel_seconds, roofline_share

PATTERN = r"%fused_irls_pallas(\.\d+)? = "


def read(ctx):
    if ctx.trace is None:
        return None
    secs = kernel_seconds(ctx.trace, PATTERN)
    flops, nbytes = ctx.cell.kernel_work(ctx.traced).get("irls", (0, 0))
    return roofline_share(ctx.peaks, flops, nbytes, secs)
