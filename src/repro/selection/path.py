"""Secure cross-validated regularization paths as batched multi-round graphs.

The sweep advances C = (λ-chunk x K folds) path configs at once through the
existing Shamir pipeline, everything batched and jit-resident:

* **one pass over the data per round** — fold masks compose onto the
  packed batch's ragged row masks (``batched_cv_summaries``), so every
  config's train-fold (H, g, dev) AND held-out deviance/accuracy come out
  of a single streaming launch; no per-fold repacking of X ever happens.
* **one protocol launch per phase per round** — the (C, S)-leading summary
  tree goes through ``SecureCollective.secure_round_multiconfig``: one
  encode+share launch over the C*S flat slices, one exact uint64
  reduction over the institution axis per config, one Lagrange+CRT reveal
  of the C global aggregates.  Held-out metrics ride in the same protected
  buffer — no center ever sees a per-institution validation score.
* **scan-resident rounds** — ``rounds_per_sync`` Newton rounds run as one
  ``lax.scan`` per host sync, with the per-round protect rng folded
  IN-GRAPH from a single path key (``fold_in(key, round_slot)``; no host
  re-splitting, the ROADMAP follow-up this retires for the selection
  path).  Converged configs freeze (their betas stop updating, matching
  the sequential drivers' break-before-update semantics) and once a whole
  chunk has converged the remaining scan slots skip the round body via
  ``lax.cond``, so overshoot costs nothing.  The deviance trace comes
  back in (rounds_per_sync, C) blocks — the only host transfer.
* **warm starts along the path** — the λ grid (sorted descending, the
  glmnet direction) is processed in chunks of ``lam_block`` path points;
  each chunk's fold iterates initialize from the previous chunk's
  converged fold betas, which is what collapses late-path Newton counts
  to 2-3 rounds.  ``lam_block=len(lambdas)`` degenerates to the fully
  amortized single-batch sweep (every path point in every launch);
  ``lam_block=1`` maximizes warm-start reuse.  Both shapes converge to
  the same per-config fixed points — Newton's answer does not depend on
  its starting point — so the precision contract vs the per-(λ, fold)
  sequential oracle is the summaries ladder's converged-beta contract.

The final refit runs through the SAME machinery: a trailing 1-config
chunk with ``fold == -1`` (no held-out rows — the masks reduce to the
plain row masks) at the 1-SE λ, warm-started from that λ's fold betas.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.batched_summaries import (
    BACKENDS as SUMMARY_BACKENDS,
    PackedPartitions,
    batched_cv_summaries,
    pack_partitions,
)
from ..core.collective import SecureCollective, declassify_sum
from ..core.newton import (
    newton_step,
    prox_newton_step,
    regularized_objective,
    should_stop,
)
from ..core.scanfit import scan_rounds
from ..obs import metrics as _metrics
from ..obs.trace import traced as _traced
from .folds import assign_folds, pack_fold_ids
from .report import PathReport, one_se_rule

__all__ = ["PathSettings", "PathDriver", "secure_cv_path"]

PROTECT_CHOICES = ("none", "gradient", "hessian", "both")


def _batched_update(betas, H, g, lams, l1: float):
    """vmapped Newton / prox-Newton step, per-config λ."""
    H = jnp.asarray(H, jnp.float64)
    g = jnp.asarray(g, jnp.float64)
    if l1 == 0.0:
        return jax.vmap(newton_step)(betas, H, g, lams)
    return jax.vmap(
        lambda b, Hc, gc, lc: prox_newton_step(b, Hc, gc, lc, l1)
    )(betas, H, g, lams)


@functools.partial(
    jax.jit,
    static_argnames=("agg", "protect", "l1", "tol", "points",
                     "summaries_backend", "num_rounds", "num_parts",
                     "max_rounds"),
)
def _cv_sweep_block(betas, obj_prev, converged, iters, vdev, vcorr, vcnt,
                    key, round_base, X, X32, y, counts, fold_ids, fold_of,
                    lams, agg: SecureCollective, protect: str, l1: float,
                    tol: float,
                    points: tuple[int, ...] | None,
                    summaries_backend: str, num_rounds: int,
                    num_parts: int, max_rounds: int):
    """``num_rounds`` secure sweep rounds as ONE jitted lax.scan.

    Carry: per-config (betas, obj_prev, converged, iters, held-out
    stats) plus the global round slot counter that folds the protect rng
    in-graph.  Emits the (num_rounds, C) objective/active trace blocks —
    the caller's only readback.  The ``max_rounds`` budget is enforced
    IN-GRAPH per config: no config ever executes a round past it
    regardless of the scan block length, and a config spending its last
    budgeted round keeps the beta its revealed metrics were measured at
    (so an unconverged config's reported beta and CV metrics always
    correspond — the same break-before-update shape convergence uses).
    """
    packed = PackedPartitions(X, X32, y, counts)
    scale = agg.codec.scale

    def round_fn(carry):
        betas, obj_prev, converged, iters, vdev, vcorr, vcnt, slot = carry
        kr = agg.round_key(key, slot)
        sm = batched_cv_summaries(
            betas, packed, fold_ids, fold_of,
            backend=summaries_backend,
        )
        tree = {}
        if protect in ("gradient", "both"):
            tree["gradient"] = sm.gradient
        if protect in ("hessian", "both"):
            tree["hessian"] = sm.hessian
        if protect != "none":
            tree["deviance"] = sm.deviance
            tree["count"] = sm.count
            tree["val_deviance"] = sm.val_deviance
            tree["val_correct"] = sm.val_correct
            tree["val_count"] = sm.val_count
            revealed = agg.secure_round_multiconfig(kr, tree, points=points)
        else:
            revealed = {}
        # unprotected leaves leave the round ONLY as cross-institution
        # sums (axis 1 of the (C, S, ...) summaries) — the annotated
        # declassification the static gate checks
        H = revealed["hessian"] if protect in ("hessian", "both") \
            else declassify_sum(sm.hessian, axis=1)
        g = revealed["gradient"] if protect in ("gradient", "both") \
            else declassify_sum(sm.gradient, axis=1)
        dev = revealed["deviance"] if protect != "none" \
            else declassify_sum(sm.deviance, axis=1)
        vdev_r = revealed.get("val_deviance",
                              declassify_sum(sm.val_deviance, axis=1))
        vcorr_r = revealed.get("val_correct",
                               declassify_sum(sm.val_correct, axis=1))
        vcnt_r = revealed.get("val_count",
                              declassify_sum(sm.val_count, axis=1))
        obj = regularized_objective(dev, betas, lams, l1)  # (C,)
        active = ~converged & (iters < max_rounds)
        # the one stopping rule, vectorized over the config axis
        stop = should_stop(obj_prev, obj, tol, num_parts, scale)
        conv_new = converged | (active & stop)
        beta_new = _batched_update(betas, H, g, lams, l1)
        # sequential break-before-update semantics: a config that stops
        # this round — or spends its last budgeted round — keeps the
        # beta its objective and held-out metrics were measured at
        exhausting = active & (iters + 1 >= max_rounds)
        freeze = conv_new | exhausting | ~active
        betas = jnp.where(freeze[:, None], betas, beta_new)
        obj_prev = jnp.where(freeze, obj_prev, obj)
        iters = iters + active.astype(jnp.int32)
        # held-out stats freeze at the stopping round's (= reported
        # beta's) values; they keep tracking while the config moves
        vdev = jnp.where(active, vdev_r, vdev)
        vcorr = jnp.where(active, vcorr_r, vcorr)
        vcnt = jnp.where(active, vcnt_r, vcnt)
        return ((betas, obj_prev, conv_new, iters, vdev, vcorr, vcnt,
                 slot + 1), (obj, active))

    def skip_fn(carry):
        # whole chunk converged/out of budget: remaining slots are free
        (betas, obj_prev, converged, iters, vdev, vcorr, vcnt,
         slot) = carry
        return ((betas, obj_prev, converged, iters, vdev, vcorr, vcnt,
                 slot + 1),
                (obj_prev, jnp.zeros_like(converged)))

    def settled(carry):
        return jnp.all(carry[2] | (carry[3] >= max_rounds))

    carry0 = (betas, obj_prev, converged, iters, vdev, vcorr, vcnt,
              round_base)
    carry, (objs, actives) = scan_rounds(
        round_fn, skip_fn, settled, carry0, num_rounds
    )
    return carry, objs, actives


@dataclasses.dataclass(frozen=True)
class PathSettings:
    """Static configuration of one λ-path sweep (hashable; the jit keys)."""

    lambdas: tuple[float, ...]  # DESCENDING
    num_folds: int = 5
    l1: float = 0.0
    protect: str = "gradient"
    tol: float = 1e-10
    summaries_backend: str = "pallas"
    lam_block: int = 1
    rounds_per_sync: int = 8
    max_rounds: int = 50
    warm_start: bool = True
    refit: bool = True
    seed: int = 0
    fold_seed: int = 0

    def __post_init__(self):
        if len(self.lambdas) == 0:
            raise ValueError("need at least one lambda")
        if any(a <= b for a, b in zip(self.lambdas, self.lambdas[1:])):
            raise ValueError(
                "lambdas must be strictly descending (duplicates would "
                "run identical configs through every secure round)"
            )
        if self.protect not in PROTECT_CHOICES:
            raise ValueError(f"protect must be one of {PROTECT_CHOICES}")
        if self.summaries_backend not in SUMMARY_BACKENDS:
            raise ValueError(
                f"summaries_backend must be one of {SUMMARY_BACKENDS}"
            )
        if not (1 <= self.lam_block <= len(self.lambdas)):
            raise ValueError("lam_block must be in 1..len(lambdas)")
        if self.rounds_per_sync < 1:
            raise ValueError("rounds_per_sync must be >= 1")
        if self.max_rounds < 1:
            raise ValueError(
                "max_rounds must be >= 1 (0 would 'run' the sweep without "
                "a single secure round and report all-zero betas)"
            )
        if self.num_folds < 2:
            raise ValueError("need at least 2 folds")


class PathDriver:
    """Chunked execution of a PathSettings sweep over caller-supplied parts.

    The driver is deliberately split from data access: each chunk takes
    the *current* partitions + per-institution fold ids (the
    ``SelectionCoordinator`` re-forms its cohort per chunk; the
    functional ``secure_cv_path`` passes the same parts every time), so
    membership churn between chunks composes with the churn-safe fold
    assignment.  All cross-chunk state lives in a plain dict of numpy
    arrays — that dict IS the mid-path checkpoint.
    """

    def __init__(self, settings: PathSettings, agg: SecureCollective):
        if agg.backend != "pallas":
            raise ValueError(
                "the selection sweep requires the pallas backend (the flat "
                "share buffers ARE the batched multi-config wire format)"
            )
        self.settings = settings
        self.agg = agg
        self.key = jax.random.PRNGKey(settings.seed)

    # -- chunk schedule -------------------------------------------------------
    def chunks(self) -> list[tuple[int, ...]]:
        s = self.settings
        L = len(s.lambdas)
        out = [tuple(range(i, min(i + s.lam_block, L)))
               for i in range(0, L, s.lam_block)]
        return out

    def num_chunks(self) -> int:
        # +1: the trailing full-data refit chunk at the selected λ
        return len(self.chunks()) + (1 if self.settings.refit else 0)

    # -- state ----------------------------------------------------------------
    def fresh_state(self) -> dict:
        s = self.settings
        L, K = len(s.lambdas), s.num_folds
        return {
            "next_chunk": np.asarray(0),
            "warm": np.zeros((0, 0)),  # (K, d) once known
            "fold_betas": np.zeros((0,)),  # (L, K, d) once d known
            "fold_rounds": np.zeros((L, K), np.int32),
            "fold_converged": np.zeros((L, K), bool),
            "val_deviance": np.zeros((L, K)),
            "val_correct": np.zeros((L, K)),
            "val_count": np.zeros((L, K)),
            "round_base": np.asarray(0),
            "rounds_total": np.asarray(0),
            "bytes_total": np.asarray(0, np.int64),
            "bytes_per_round": np.asarray(0, np.int64),
            "beta": np.zeros((0,)),  # refit result
            "refit_rounds": np.asarray(0),
            "refit_converged": np.asarray(False),
        }

    def finished(self, state: dict) -> bool:
        return int(state["next_chunk"]) >= self.num_chunks()

    # -- one chunk ------------------------------------------------------------
    @_traced("selection")
    def run_chunk(self, state: dict, parts: Sequence, fold_parts: Sequence,
                  points: Sequence[int] | None = None,
                  num_live_centers: int | None = None,
                  traces: list | None = None) -> dict:
        """Advance the sweep by one λ chunk (or the final refit chunk).

        ``parts``/``fold_parts`` describe the current cohort;
        ``points``/``num_live_centers`` are the coordinator's live-center
        hooks (None: secure_fit-style defaults).  ``traces`` (optional
        list) receives the per-block objective readbacks.
        """
        s = self.settings
        chunk_idx = int(state["next_chunk"])
        schedule = self.chunks()
        if chunk_idx >= self.num_chunks():
            return state
        is_refit = chunk_idx >= len(schedule)

        packed = pack_partitions(parts)
        fold_ids = pack_fold_ids(fold_parts, packed.X.shape[1])
        d = packed.dim
        K = s.num_folds
        if state["fold_betas"].size == 0:
            state["fold_betas"] = np.zeros((len(s.lambdas), K, d))
        if state["warm"].size == 0:
            state["warm"] = np.zeros((K, d))

        if is_refit:
            lam_idx: tuple[int, ...] = ()
            pick = self._one_se_index(state)
            lams = np.asarray([s.lambdas[pick]])
            fold_of = np.asarray([-1], np.int32)
            # warm-start the full-data fit from that λ's mean fold beta
            betas0 = np.mean(state["fold_betas"][pick], axis=0,
                             keepdims=True)
            cfg_rows = 1
        else:
            lam_idx = schedule[chunk_idx]
            lams = np.repeat(np.asarray(s.lambdas)[list(lam_idx)], K)
            fold_of = np.tile(np.arange(K, dtype=np.int32), len(lam_idx))
            if s.warm_start:
                betas0 = np.tile(state["warm"][None], (len(lam_idx), 1, 1)
                                 ).reshape(-1, d)
            else:
                betas0 = np.zeros((len(lam_idx) * K, d))
            cfg_rows = len(lam_idx) * K

        bytes_per_round = self.agg.round_bytes(
            d, packed.num_institutions, s.protect,
            include_count=True, num_live_centers=num_live_centers,
            num_configs=cfg_rows, extra_scalars=3,
        )
        if not is_refit:
            # the report's representative wire figure: one sweep round of
            # a full (λ-chunk x cohort) batch (the refit chunk is a
            # 1-config tail and accounts into bytes_total only)
            state["bytes_per_round"] = np.asarray(bytes_per_round,
                                                  np.int64)

        carry = (
            jnp.asarray(betas0, jnp.float64),
            jnp.full((cfg_rows,), np.inf, jnp.float64),
            jnp.zeros((cfg_rows,), bool),
            jnp.zeros((cfg_rows,), jnp.int32),
            jnp.zeros((cfg_rows,), jnp.float64),
            jnp.zeros((cfg_rows,), jnp.float64),
            jnp.zeros((cfg_rows,), jnp.float64),
            jnp.asarray(int(state["round_base"]), jnp.int32),
        )
        lams_j = jnp.asarray(lams, jnp.float64)
        fold_of_j = jnp.asarray(fold_of, jnp.int32)
        pts = tuple(points) if points is not None else None
        if s.protect == "none":
            pts = None
        chunk_trace = []
        executed = 0
        while True:
            carry, objs, actives = _cv_sweep_block(
                *carry[:7], self.key, carry[7], packed.X, packed.X32,
                packed.y, packed.counts, fold_ids, fold_of_j, lams_j,
                agg=self.agg, protect=s.protect, l1=float(s.l1),
                tol=float(s.tol), points=pts,
                summaries_backend=s.summaries_backend,
                num_rounds=s.rounds_per_sync,
                num_parts=packed.num_institutions,
                max_rounds=s.max_rounds,
            )
            # host-sync: the block's ONE readback — trace + carry in a
            # single transfer (the carry itself stays on device for the
            # next block dispatch)
            (objs, actives, betas_f, conv_f, iters_f, vdev_f, vcorr_f,
             vcnt_f, base_f) = jax.device_get(
                (objs, actives, carry[0], carry[2], carry[3], carry[4],
                 carry[5], carry[6], carry[7])
            )
            chunk_trace.append(objs)
            executed += int(actives.any(axis=1).sum())
            if bool(conv_f.all()) or int(iters_f.max()) >= s.max_rounds:
                break

        state["round_base"] = np.asarray(int(base_f))
        state["rounds_total"] = np.asarray(
            int(state["rounds_total"]) + executed
        )
        state["bytes_total"] = np.asarray(
            int(state["bytes_total"]) + executed * bytes_per_round,
            np.int64,
        )
        if executed:
            _metrics.observe_round("selection_path", bytes_per_round,
                                   rounds=executed)
        if traces is not None:
            traces.append({
                "chunk": chunk_idx,
                "lambdas": lams.copy(),
                "objectives": np.concatenate(chunk_trace, axis=0),
            })
        if is_refit:
            state["beta"] = betas_f[0]
            state["refit_rounds"] = np.asarray(int(iters_f[0]))
            state["refit_converged"] = np.asarray(bool(conv_f[0]))
        else:
            by_lam = betas_f.reshape(len(lam_idx), K, d)
            for row, li in enumerate(lam_idx):
                state["fold_betas"][li] = by_lam[row]
                state["fold_rounds"][li] = iters_f.reshape(-1, K)[row]
                state["fold_converged"][li] = conv_f.reshape(-1, K)[row]
                state["val_deviance"][li] = vdev_f.reshape(-1, K)[row]
                state["val_correct"][li] = vcorr_f.reshape(-1, K)[row]
                state["val_count"][li] = vcnt_f.reshape(-1, K)[row]
            # warm-start source for the next chunk: the LAST (smallest)
            # λ of this chunk, the path neighbour of the next chunk
            state["warm"] = by_lam[-1].copy()
        state["next_chunk"] = np.asarray(chunk_idx + 1)
        return state

    # -- reporting ------------------------------------------------------------
    def _cv_curve(self, state: dict):
        vcnt = np.maximum(state["val_count"], 1.0)
        per_rec = state["val_deviance"] / vcnt  # (L, K)
        cv_mean = per_rec.mean(axis=1)
        cv_se = per_rec.std(axis=1, ddof=1) / np.sqrt(per_rec.shape[1])
        cv_acc = (state["val_correct"].sum(axis=1)
                  / np.maximum(state["val_count"].sum(axis=1), 1.0))
        return cv_mean, cv_se, cv_acc

    def _one_se_index(self, state: dict) -> int:
        cv_mean, cv_se, _ = self._cv_curve(state)
        _, pick = one_se_rule(
            np.asarray(self.settings.lambdas), cv_mean, cv_se
        )
        return pick

    def build_report(self, state: dict, traces: list | None = None
                     ) -> PathReport:
        s = self.settings
        cv_mean, cv_se, cv_acc = self._cv_curve(state)
        best, pick = one_se_rule(np.asarray(s.lambdas), cv_mean, cv_se)
        return PathReport(
            lambdas=np.asarray(s.lambdas),
            l1=s.l1,
            num_folds=s.num_folds,
            protect=s.protect,
            summaries_backend=s.summaries_backend,
            fold_betas=state["fold_betas"].copy(),
            fold_rounds=state["fold_rounds"].copy(),
            fold_converged=state["fold_converged"].copy(),
            val_deviance=state["val_deviance"].copy(),
            val_correct=state["val_correct"].copy(),
            val_count=state["val_count"].copy(),
            cv_mean=cv_mean,
            cv_se=cv_se,
            cv_accuracy=cv_acc,
            best_index=best,
            lambda_best=float(s.lambdas[best]),
            one_se_index=pick,
            lambda_1se=float(s.lambdas[pick]),
            beta=(state["beta"].copy() if state["beta"].size else None),
            refit_rounds=int(state["refit_rounds"]),
            rounds_total=int(state["rounds_total"]),
            bytes_per_round=int(state["bytes_per_round"]),
            bytes_total=int(state["bytes_total"]),
            traces=list(traces) if traces is not None else [],
        )


def secure_cv_path(
    parts: Sequence,
    lambdas: Sequence[float],
    num_folds: int = 5,
    l1: float = 0.0,
    protect: str = "gradient",
    aggregator: SecureCollective | None = None,
    tol: float = 1e-10,
    seed: int = 0,
    fold_seed: int = 0,
    summaries_backend: str = "pallas",
    lam_block: int = 1,
    rounds_per_sync: int = 8,
    max_rounds: int = 50,
    warm_start: bool = True,
    refit: bool = True,
) -> PathReport:
    """Run the whole secure CV λ-path over fixed (X_j, y_j) partitions.

    The in-process mirror of ``SelectionCoordinator.run_path`` (which
    adds fault tolerance, churn, and resume): K-fold cross-validated
    held-out deviance for every λ, all through the Shamir pipeline, plus
    the 1-SE-rule pick and a warm-started full-data refit at the picked
    λ.  Partitions are indexed by position for the churn-safe fold
    assignment, so the same parts always get the same folds.
    """
    settings = PathSettings(
        lambdas=tuple(sorted((float(l) for l in lambdas), reverse=True)),
        num_folds=num_folds, l1=float(l1), protect=protect, tol=tol,
        summaries_backend=summaries_backend, lam_block=lam_block,
        rounds_per_sync=rounds_per_sync, max_rounds=max_rounds,
        warm_start=warm_start, refit=refit, seed=seed, fold_seed=fold_seed,
    )
    agg = aggregator or SecureCollective(backend="pallas")
    driver = PathDriver(settings, agg)
    fold_parts = [
        assign_folds(Xj.shape[0], num_folds, j, fold_seed)
        for j, (Xj, _) in enumerate(parts)
    ]
    state = driver.fresh_state()
    traces: list = []
    while not driver.finished(state):
        state = driver.run_chunk(state, parts, fold_parts, traces=traces)
    return driver.build_report(state, traces)
