"""Device milliseconds per secure round under the program's ``pod_wire``
scope (``core/collective.py``, inside ``aggregate``): the limb split of
the pods' aggregated share buffers, their all-reduce across the chips
and the limbs' recombine, averaged over the chips, over the traced
window's executed rounds."""
from ..wire import wire_seconds


def read(ctx):
    if ctx.trace is None:
        return None
    rounds = sum(a.rounds for a in ctx.traced)
    secs = wire_seconds(ctx.trace)
    if not rounds or secs is None:
        return None
    return 1e3 * secs / rounds
