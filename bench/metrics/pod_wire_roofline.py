"""Share of its roofline that the cross-chip wire reaches: the least time
of the traced window's all-reduces (``wire.ring_bytes`` a round, one
chip's ring traffic of the share buffer as 4-byte field words, over the
chip's interconnect bandwidth, ``wire.ICI_BYTES_PER_S``) over the
device time under ``pod_wire``, averaged over the chips."""
import jax

from ..wire import ICI_BYTES_PER_S, wire_seconds


def read(ctx):
    if ctx.trace is None:
        return None
    secs = wire_seconds(ctx.trace)
    nbytes = ctx.cell.kernel_work(ctx.traced).get("pod_wire", 0.0)
    if secs is None or secs <= 0 or nbytes <= 0:
        return None
    least = nbytes / ICI_BYTES_PER_S[jax.devices()[0].device_kind]
    return 100.0 * least / secs
