"""Whole-fit scan residency: shared ``lax.scan`` round machinery.

PR 4 made the *selection* sweep scan-resident; this module extracts that
round-graph shape so every secure driver can use it:

* :func:`scan_rounds` — the generic skeleton: ``num_rounds`` slots of
  ``lax.cond(settled, skip, round)`` under one ``lax.scan``.  The round
  body folds the protect rng IN-GRAPH from a single key and the slot
  counter (``fold_in(key, slot)``), so a whole block of secure Newton
  rounds runs without re-entering Python: one host sync per block (the
  trace readback) instead of one per round.  Skipped slots still advance
  the slot counter, which makes the rng fold of executed round r equal
  to ``fold_in(key, r)`` regardless of how the fit was cut into blocks —
  and therefore makes ``state_dict`` resume mid-scan bit-identical to an
  uninterrupted run.
* :func:`fit_scan_block` — the single-config secure fit round under that
  skeleton: batched summaries -> batched protect -> exact uint64
  share-sum (Algorithm 2) -> reveal of the global aggregate ->
  prox/Newton update, with the ``should_stop``-driven freeze matching
  the sequential drivers' break-before-update semantics.  This is the
  graph behind ``SecureFitDriver(rounds="scan")`` and
  ``StudyCoordinator(rounds="scan")``; ``selection/path.py`` runs its
  multi-config variant through the same :func:`scan_rounds` skeleton.

rng-scheme note: the per-round drivers split a host key every round
(``key, sub = jax.random.split``) while the scan folds slots from one
fixed key.  The revealed aggregates are IDENTICAL either way — Shamir
reconstruction cancels the sharing polynomials exactly in the field, so
the revealed field elements (and hence every objective float and beta)
do not depend on the rng stream at all.  Tests pin the scanned drivers
against the per-round oracles at fixed-point-quantization tolerance.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .batched_summaries import PackedPartitions, batched_local_summaries
from .collective import SecureCollective

__all__ = ["scan_rounds", "fit_scan_block"]


def scan_rounds(round_fn, skip_fn, settled_fn, carry0, num_rounds: int):
    """``num_rounds`` round slots as ONE ``lax.scan`` with early-skip.

    Each slot runs ``round_fn(carry)`` unless ``settled_fn(carry)`` is
    already True, in which case ``skip_fn(carry)`` advances the slot for
    free — overshooting a converged fit costs nothing.  Both callables
    return ``(carry, emit)`` with identical structures (the scan's
    stacked emits are the caller's one readback per block).
    """

    def body(carry, _):
        return jax.lax.cond(settled_fn(carry), skip_fn, round_fn, carry)

    return jax.lax.scan(body, carry0, None, length=num_rounds)


@functools.partial(
    jax.jit,
    static_argnames=("agg", "protect", "l1", "tol", "points",
                     "include_count", "summaries_backend", "num_rounds",
                     "num_parts", "max_rounds"),
)
def fit_scan_block(beta, obj_prev, converged, iters, key, round_base,
                   X, X32, slices, y, counts, lam,
                   agg: SecureCollective, protect: str, l1: float,
                   tol: float,
                   points: tuple[int, ...] | None,
                   include_count: bool, summaries_backend: str,
                   num_rounds: int, num_parts: int, max_rounds: int):
    """``num_rounds`` secure Newton rounds as ONE jitted ``lax.scan``.

    The single-λ mirror of the selection sweep's ``_cv_sweep_block``:
    every slot runs the full protect -> aggregate -> reveal -> Newton
    round in-graph, with the protect rng folded from ``(key, slot)``.
    ``X``, ``X32``, ``slices``, ``y``, ``counts`` are the pack's fields
    (``PackedPartitions``; ``slices`` is None unless the compiled
    ``pallas`` rung reads them), constants of the whole fit.  Returns
    ``(carry, objs, actives, grad_norms, step_norms)`` where carry is
    ``(beta, obj_prev, converged, iters, slot)`` and the
    ``(num_rounds,)`` objective/active/metric traces are the caller's
    only host readback.  The metric leaves (||revealed global
    gradient||, ||beta_new - beta|| per executed slot; 0.0 on skipped
    slots) are ALWAYS emitted — they derive from already-revealed
    aggregates, so the graph is identical whether or not observability
    consumes them.

    Semantics pinned to the per-round drivers:

    * a round that trips ``should_stop`` keeps the beta its objective was
      measured at (break-before-update) and flips ``converged``;
    * a round that spends the last budgeted slot (``iters`` reaching
      ``max_rounds``) WITHOUT converging still applies its Newton update
      — exactly what ``SecureFitDriver.run()`` leaves behind when the
      iteration limit ends the loop;
    * ``iters`` counts executed rounds (the stopping round included),
      matching ``driver.iteration``; the slot counter advances every
      slot, executed or skipped, so the rng fold of round r is always
      ``fold_in(key, round_base + r)``.
    """
    from .newton import (
        _protected_tree,
        prox_newton_step,
        regularized_objective,
        should_stop,
    )
    from .collective import declassify_sum

    packed = PackedPartitions(X, X32, y, counts, slices)
    scale = agg.codec.scale

    def round_fn(carry):
        beta, obj_prev, converged, iters, slot = carry
        kr = agg.round_key(key, slot)
        sm = batched_local_summaries(
            beta, packed, backend=summaries_backend,
        )
        tree = _protected_tree(protect, sm.hessian, sm.gradient,
                               sm.deviance)
        if tree and include_count:
            tree["count"] = counts.astype(jnp.float64)
        revealed = agg.secure_round_batched(kr, tree, points=points) \
            if tree else {}
        # unprotected leaves leave the round ONLY as cross-institution
        # sums — the annotated declassification the static gate checks
        H = revealed["hessian"] if protect in ("hessian", "both") \
            else declassify_sum(sm.hessian, axis=0)
        g = revealed["gradient"] if protect in ("gradient", "both") \
            else declassify_sum(sm.gradient, axis=0)
        dev = revealed["deviance"] if protect != "none" \
            else declassify_sum(sm.deviance, axis=0)
        obj = regularized_objective(dev, beta, lam, l1)
        active = ~converged & (iters < max_rounds)
        stop = should_stop(obj_prev, obj, tol, num_parts, scale)
        conv_new = converged | (active & stop)
        beta_new = prox_newton_step(
            beta, jnp.asarray(H, jnp.float64), jnp.asarray(g, jnp.float64),
            lam, l1,
        )
        freeze = conv_new | ~active
        # PUBLIC metric leaves riding the existing trace readback: both
        # derive from the revealed global aggregate, never from shares
        gnorm = jnp.linalg.norm(jnp.asarray(g, jnp.float64))
        snorm = jnp.linalg.norm(beta_new - beta)
        beta = jnp.where(freeze, beta, beta_new)
        obj_prev = jnp.where(freeze, obj_prev, obj)
        iters = iters + active.astype(jnp.int32)
        return ((beta, obj_prev, conv_new, iters, slot + 1),
                (obj, active, gnorm, snorm))

    def skip_fn(carry):
        beta, obj_prev, converged, iters, slot = carry
        zero = jnp.zeros((), jnp.float64)
        return ((beta, obj_prev, converged, iters, slot + 1),
                (obj_prev, jnp.zeros((), bool), zero, zero))

    def settled(carry):
        return carry[2] | (carry[3] >= max_rounds)

    carry0 = (beta, obj_prev, converged, iters, round_base)
    carry, (objs, actives, grad_norms, step_norms) = scan_rounds(
        round_fn, skip_fn, settled, carry0, num_rounds
    )
    return carry, objs, actives, grad_norms, step_norms
