"""Device milliseconds per secure round under ``newton_solve``
(``core/newton.py``): the Cholesky factorisation and solve of the
regularised Hessian on the revealed aggregates, in float64."""
from ..scopes import per_round_ms


def read(ctx):
    return per_round_ms(ctx, ("newton_solve",))
