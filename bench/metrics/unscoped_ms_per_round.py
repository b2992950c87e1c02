"""Device milliseconds per secure round under none of the program's
scopes: the scan and cond plumbing, the carry selects, the objective,
the stopping rule, the rng fold, and every copy XLA inserts, which
carries no ``op_name`` whatever it copies.  In ``d128-fit`` most of it
is such a copy, ``%copy`` of the ``summaries/f64_terms`` buffer (the
f64 split of X) around its in-place update in the loop, so a change to
the f64 terms can move this metric and not ``f64_terms_ms_per_round``.
With the three other ``*_ms_per_round`` metrics of the top scopes it
makes up the window's leaf-op time."""
from ..scopes import UNSCOPED, per_round_ms


def read(ctx):
    return per_round_ms(ctx, (UNSCOPED,))
