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
  Given a pod mesh (``StudyCoordinator(pods=...)``) the same round runs
  each device's institutions under ``shard_map`` and sums the pods'
  share buffers on the exact cross-chip wire.

rng-scheme note: the per-round drivers split a host key every round
(``key, sub = jax.random.split``) while the scan folds slots from one
fixed key.  The revealed aggregates are IDENTICAL either way — Shamir
reconstruction cancels the sharing polynomials exactly in the field, so
the revealed field elements (and hence every objective float and beta)
do not depend on the rng stream at all.  Tests pin the scanned drivers
against the per-round oracles at fixed-point-quantization tolerance.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from ..distributed.sharding import POD_AXIS
from .batched_summaries import PackedPartitions, batched_local_summaries
from .collective import SecureCollective

__all__ = ["scan_rounds", "fit_scan_block", "pod_partitioner"]


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


def pod_partitioner(mesh):
    """The partitioner the fit program is lowered and compiled with.

    One device: the default.  A pod mesh: GSPMD, because XLA:TPU refuses
    a float64 Cholesky in a program that Shardy partitions over several
    devices ("A tuple parameter that is being flattened shouldn't have
    frontend attributes"), and the pod fit's Newton solve is the one
    the one-device fit runs.  :func:`fit_scan_block` enters it for its
    call; a caller that lowers and compiles the pod program itself
    enters it around both.
    """
    if mesh is None:
        return contextlib.nullcontext()
    from jax._src import config

    return config.use_shardy_partitioner(False)


def fit_scan_block(*args, mesh=None, **kwargs):
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

    ``mesh``: a pod mesh (``distributed.multihost.pod_mesh``) over whose
    ``POD_AXIS`` the pack's institution axis is sharded
    (``pack_partitions(mesh=)``).  Each round then takes every device's
    own institutions through summaries, protect and the local share sum
    under ``shard_map``, sums the pods' share buffers with one exact
    all-reduce and reveals on every device alike
    (``SecureCollective.secure_round_batched(axis_name=)``); the Newton
    solve and the stopping rule run on the replicated aggregates, so
    every device holds the same beta and ``converged``.  ``None`` (one
    device) traces no collective.

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

    Arguments: ``(beta, obj_prev, converged, iters, key, round_base, X,
    X32, slices, y, counts, lam)``, then the static ``agg, protect, l1,
    tol, points, include_count, summaries_backend, num_rounds,
    num_parts, max_rounds`` by name.
    """
    with pod_partitioner(mesh):
        return _fit_scan_block(*args, mesh=mesh, **kwargs)


def _fit_scan_block(beta, obj_prev, converged, iters, key, round_base,
                    X, X32, slices, y, counts, lam,
                    agg: SecureCollective, protect: str, l1: float,
                    tol: float,
                    points: tuple[int, ...] | None,
                    include_count: bool, summaries_backend: str,
                    num_rounds: int, num_parts: int, max_rounds: int,
                    mesh=None):
    from .newton import (
        _protected_tree,
        prox_newton_step,
        regularized_objective,
        should_stop,
    )
    from .collective import declassify_sum

    scale = agg.codec.scale
    axis_name = None if mesh is None else POD_AXIS
    if mesh is not None and protect != "both":
        raise ValueError("a pod mesh carries shares only: protect='both'")

    def aggregates(beta, kr, X, X32, slices, y, counts):
        """Summaries, protect and share sum of the institutions at hand,
        then the revealed (or annotated plaintext) global sums."""
        sm = batched_local_summaries(
            beta, PackedPartitions(X, X32, y, counts, slices),
            backend=summaries_backend,
        )
        tree = _protected_tree(protect, sm.hessian, sm.gradient,
                               sm.deviance)
        if tree and include_count:
            tree["count"] = counts.astype(jnp.float64)
        revealed = agg.secure_round_batched(
            kr, tree, points=points, axis_name=axis_name) if tree else {}
        # unprotected leaves leave the round ONLY as cross-institution
        # sums — the annotated declassification the static gate checks
        H = revealed["hessian"] if protect in ("hessian", "both") \
            else declassify_sum(sm.hessian, axis=0)
        g = revealed["gradient"] if protect in ("gradient", "both") \
            else declassify_sum(sm.gradient, axis=0)
        dev = revealed["deviance"] if protect != "none" \
            else declassify_sum(sm.deviance, axis=0)
        return H, g, dev

    if mesh is not None:
        # only the institutions' side is a manual computation: XLA:TPU
        # refuses a float64 Cholesky inside one
        from jax.sharding import PartitionSpec as P

        public, sites = P(), P(POD_AXIS)
        aggregates = jax.shard_map(
            aggregates, mesh=mesh, in_specs=(public, public) + (sites,) * 5,
            out_specs=public, check_vma=False,
        )

    def round_fn(carry):
        beta, obj_prev, converged, iters, slot = carry
        kr = agg.round_key(key, slot)
        H, g, dev = aggregates(beta, kr, X, X32, slices, y, counts)
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


# the program keeps its name in traces and in HLO: ``jit(fit_scan_block)``
_fit_scan_block.__name__ = _fit_scan_block.__qualname__ = "fit_scan_block"
_fit_scan_block = jax.jit(
    _fit_scan_block,
    static_argnames=("agg", "protect", "l1", "tol", "points",
                     "include_count", "summaries_backend", "num_rounds",
                     "num_parts", "max_rounds", "mesh"),
)
fit_scan_block.lower = _fit_scan_block.lower
