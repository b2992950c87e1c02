"""Device milliseconds per secure round under the program's ``summaries``
scope and its ``summaries/*`` parts (``core/batched_summaries.py``: the
Gram, the f64 gradient and deviance terms, the per-call pad and cast of
the rows), over the traced window's executed rounds."""
from ..scopes import per_round_ms


def read(ctx):
    return per_round_ms(ctx, ("summaries",))
