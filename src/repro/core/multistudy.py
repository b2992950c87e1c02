"""Slot-packed multi-study rounds: the seed of the study server.

ROADMAP direction 1's serving layer wants one deployment to advance
MANY independent studies (cohort x lambda x protect-mode combinations)
without paying one secure round per study.  The
:class:`repro.core.collective.SecureCollective` multiconfig wire makes
that a packing exercise: a study is just one more leading slot axis on
the summary tree, exactly like the selection sweep's (lambda x fold)
config axis.  :func:`fused_multistudy_iteration` advances M independent
cohorts by ONE collective round on a shared (study-slot, S, ...) batch:

* per-study batched summaries (one fused-IRLS launch per study — the
  studies have different betas, so the summaries cannot share a launch),
* ONE encode+share launch over the (M * S) flat slices, ONE exact
  uint64 reduction over the institution axis per slot, ONE Lagrange+CRT
  reveal of the M per-study aggregates
  (``SecureCollective.secure_round_multiconfig`` with the study slot as
  the config axis),
* per-study Newton/prox updates on the revealed aggregates.

Because Shamir reconstruction cancels the sharing polynomials exactly
in the field, each slot's revealed aggregate is the same field decode an
independent per-study round would produce — so a slot-packed fit
matches M independent fits to fixed-point quantization (pinned in
``tests/test_collective.py``).  Privacy is unchanged: slots are
independent payload lanes of the one certified chain; no cross-study
term ever forms, and only per-study cross-institution aggregates are
revealed.

Studies with different cohort sizes pack by padding: extra institutions
enter with ``count=0`` (their masked summaries are exactly zero, which
encodes to the zero field element and drops out of the aggregate), and
shorter record axes zero-pad below the count mask.  See
:func:`stack_studies`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .batched_summaries import (
    PackedPartitions,
    batched_local_summaries,
    pack_partitions,
)
from .collective import SecureCollective, declassify_sum
from .newton import (
    _protected_tree,
    prox_newton_step,
    regularized_objective,
)

__all__ = ["stack_studies", "fused_multistudy_iteration",
           "run_multistudy_rounds"]


def stack_studies(studies) -> PackedPartitions:
    """Stack M studies' partition lists into one (M, S, N, d) batch.

    ``studies`` is a sequence of per-study partition lists (each a list
    of ``(X_j, y_j)`` pairs, as :func:`pack_partitions` takes).  Ragged
    studies are padded to the widest cohort and the longest record axis:
    padding institutions carry ``count=0`` and all-zero rows, so their
    masked summaries are exactly zero and vanish from every aggregate —
    the packed fit stays bit-equal to the unpadded one.
    """
    if not studies:
        raise ValueError("need at least one study")
    packs = [pack_partitions(list(parts)) for parts in studies]
    d = packs[0].X.shape[-1]
    if any(p.X.shape[-1] != d for p in packs):
        raise ValueError("all studies must share the feature dimension")
    s_max = max(p.X.shape[0] for p in packs)
    n_max = max(p.X.shape[1] for p in packs)

    def pad(arr, s_dim, n_dim=None):
        widths = [(0, s_dim - arr.shape[0])]
        if n_dim is not None:
            widths.append((0, n_dim - arr.shape[1]))
        widths.extend([(0, 0)] * (arr.ndim - len(widths)))
        return jnp.pad(arr, widths)

    X = jnp.stack([pad(p.X, s_max, n_max) for p in packs])
    X32 = jnp.stack([pad(p.X32, s_max, n_max) for p in packs])
    y = jnp.stack([pad(p.y, s_max, n_max) for p in packs])
    counts = jnp.stack([pad(p.counts, s_max) for p in packs])
    return PackedPartitions(X, X32, y, counts)


@functools.partial(
    jax.jit, static_argnames=("agg", "protect", "l1", "points",
                              "include_count", "summaries_backend")
)
def fused_multistudy_iteration(betas, key, X, X32, y, counts, lams,
                               agg: SecureCollective, protect: str,
                               l1: float,
                               points: tuple[int, ...] | None = None,
                               include_count: bool = False,
                               summaries_backend: str = "pallas"):
    """M independent secure Newton rounds as ONE collective round.

    Arrays carry a leading study-slot axis: ``betas`` (M, d), ``lams``
    (M,), ``X``/``X32``/``y``/``counts`` as stacked by
    :func:`stack_studies`.  The per-study summaries stack into a
    (study-slot, S, ...) tree and advance through ONE
    ``secure_round_multiconfig`` — one encode+share launch, one
    per-slot institution reduction, one reveal — then each study applies
    its own prox/Newton update on its revealed aggregate.  Returns
    ``(betas_new, objectives, grad_norms, step_norms)``, each with the
    leading M axis; the scalars are the same PUBLIC metric leaves the
    single-study fused iteration emits.

    ``protect``/``l1``/``points``/``include_count`` are shared across
    slots (one wire contract per deployment); per-study lambda rides in
    ``lams``.  Unprotected leaves leave the round per slot only as
    cross-institution sums through the annotated ``declassify_sum``
    boundary, exactly as in the single-study drivers.
    """
    num_studies = X.shape[0]
    sms = [
        batched_local_summaries(
            betas[m], PackedPartitions(X[m], X32[m], y[m], counts[m]),
            backend=summaries_backend,
        )
        for m in range(num_studies)
    ]
    hessian = jnp.stack([sm.hessian for sm in sms])    # (M, S, d, d)
    gradient = jnp.stack([sm.gradient for sm in sms])  # (M, S, d)
    dev = jnp.stack([sm.deviance for sm in sms])       # (M, S)
    revealed = {}
    tree = _protected_tree(protect, hessian, gradient, dev)
    if tree and include_count:
        tree["count"] = counts.astype(jnp.float64)
    if tree:
        revealed = agg.secure_round_multiconfig(key, tree, points=points)
    global_h = revealed["hessian"] if protect in ("hessian", "both") \
        else declassify_sum(hessian, axis=1)
    global_g = revealed["gradient"] if protect in ("gradient", "both") \
        else declassify_sum(gradient, axis=1)
    global_dev = revealed["deviance"] if protect != "none" \
        else declassify_sum(dev, axis=1)

    def per_study(H, g, dv, beta, lam):
        obj = regularized_objective(dv, beta, lam, l1)
        beta_new = prox_newton_step(
            beta, jnp.asarray(H, jnp.float64), jnp.asarray(g, jnp.float64),
            lam, l1,
        )
        gnorm = jnp.linalg.norm(jnp.asarray(g, jnp.float64))
        snorm = jnp.linalg.norm(beta_new - beta)
        return beta_new, obj, gnorm, snorm

    return jax.vmap(per_study)(global_h, global_g, global_dev, betas, lams)


def run_multistudy_rounds(studies, lams, num_rounds: int,
                          aggregator: SecureCollective | None = None,
                          protect: str = "both", l1: float = 0.0,
                          key: jax.Array | None = None,
                          summaries_backend: str = "pallas"):
    """Advance M studies ``num_rounds`` rounds, one collective round each.

    Host-loop convenience over :func:`fused_multistudy_iteration` (the
    study-server seed has no convergence machinery yet — every study
    runs the full budget).  Returns ``(betas, objective_trace)`` with
    ``betas`` (M, d) and ``objective_trace`` (num_rounds, M).  The
    protect rng follows the one :meth:`SecureCollective.round_key`
    discipline — round r folds ``(key, r)`` — though the revealed
    aggregates (and hence the betas) are rng-independent either way.
    """
    agg = aggregator or SecureCollective(backend="pallas")
    packed = stack_studies(studies)
    d = packed.X.shape[-1]
    betas = jnp.zeros((len(studies), d), jnp.float64)
    lams = jnp.asarray(lams, jnp.float64)
    if key is None:
        key = jax.random.PRNGKey(0)
    trace = []
    for r in range(num_rounds):
        betas, objs, _, _ = fused_multistudy_iteration(
            betas, agg.round_key(key, r), packed.X, packed.X32, packed.y,
            packed.counts, lams, agg, protect, l1,
            summaries_backend=summaries_backend,
        )
        trace.append(objs)
    return betas, jnp.stack(trace)
