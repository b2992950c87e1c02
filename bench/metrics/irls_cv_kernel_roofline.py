"""Share of its roofline that the cross-validated summaries kernel
reaches (``_irls_cv_kernel`` in ``kernels/fused_irls.py``, whose launch
the device trace names after its wrapper, ``fused_irls_cv_pallas``): the
least time for the traced window's launches, the larger of operations
over the bfloat16 peak and bytes over HBM bandwidth (the cell's
``kernel_work``, from ``work.irls_cv_kernel``: the rows read once, not
once per configuration), over the kernel's summed device time."""
from . import kernel_seconds, roofline_share

PATTERN = r"%fused_irls_cv_pallas(\.\d+)? = "


def read(ctx):
    if ctx.trace is None:
        return None
    secs = kernel_seconds(ctx.trace, PATTERN)
    flops, nbytes = ctx.cell.kernel_work(ctx.traced).get("irls_cv", (0, 0))
    return roofline_share(ctx.peaks, flops, nbytes, secs)
