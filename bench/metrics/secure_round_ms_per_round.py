"""Device milliseconds per secure round under ``protect``, ``aggregate``
and ``reveal`` (``core/collective.py``): the fixed-point encode, the
share kernel and its random coefficients, the exact uint64 institution
reduction, the reconstruct kernel and the CRT decode."""
from ..scopes import per_round_ms


def read(ctx):
    return per_round_ms(ctx, ("protect", "aggregate", "reveal"))
