"""Device milliseconds per secure round under ``summaries/f64_terms``:
the float64 linear predictor, gradient and deviance over every padded
row (``_sim_terms`` beside the summaries kernel on the ``pallas`` rung,
``_masked_irls_terms`` on the ``reference`` rung)."""
from ..scopes import per_round_ms


def read(ctx):
    return per_round_ms(ctx, ("summaries/f64_terms",))
