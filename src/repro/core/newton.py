"""Newton-Raphson drivers: centralized gold standard + secure distributed.

``centralized_fit`` is the oracle the paper compares against (Fig. 2's "gold
standard", i.e. what R's glmnet-style IRLS would produce).  ``secure_fit``
runs the paper's Algorithm 1: per-institution summaries -> Shamir protection
-> share-wise aggregation at the Computation Centers -> reconstruction of the
*global* aggregate only -> Newton update (Eq. 3) -> deviance-based
convergence check.  Both converge to the same beta (R^2 = 1.00, Fig. 2);
tests assert this to ~1e-6 which is far below the fixed-point quantization
we configure.

Two execution shapes for the secure loop:

* **fused** (default on the pallas backend) — the whole iteration is one
  jitted graph: a single batched fused-IRLS launch over all S (ragged)
  institutions, one batched protect launch over the S flat buffers, one
  exact uint64 reduction for Algorithm 2, one reveal, and the Newton/prox
  update — the only host sync per iteration is the scalar deviance read
  for the convergence test.
* **loop** (reference backend, or ``fused=False``) — the paper-shaped
  Python loop over institutions, one protect per institution.  Kept as
  the correctness comparator and as the pre-fusion baseline that
  ``benchmarks/e2e_secure_fit.py`` measures against.

On top of the per-round shapes, ``SecureFitDriver(rounds="scan")`` runs
whole BLOCKS of fused rounds as one ``lax.scan`` (``core.scanfit``): the
protect rng folds in-graph from a single key, convergence freezes the
carry via ``lax.cond``, and the objective trace reads back once per
block — one host sync per fit (``rounds_per_sync=None``) instead of one
per round.  The per-round paths stay as the bit-exact oracles; tests
pin the scanned trajectory against them at quantization tolerance.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .batched_summaries import (
    BACKENDS as SUMMARY_BACKENDS,
    PackedPartitions,
    batched_local_summaries,
    pack_partitions,
)
from ..obs import metrics as _metrics
from ..obs.trace import traced as _traced
from .logreg import LocalSummaries, local_summaries, deviance
from .collective import SecureCollective, declassify_sum

__all__ = ["FitResult", "RoundReport", "newton_step", "prox_newton_step",
           "centralized_fit", "secure_fit", "SecureFitDriver",
           "regularized_objective", "stop_threshold", "should_stop",
           "stop_threshold_host", "should_stop_host"]

PROTECT_CHOICES = ("none", "gradient", "hessian", "both")


# -- the one stopping rule -----------------------------------------------------
#
# Every secure driver (secure_fit loop + fused, StudyCoordinator loop +
# fused rounds, and the selection sweep's in-graph scan) terminates on the
# SAME deviance test, computed from identically-formed objectives.  Before
# unification the loop drivers summed ``float(dev) + lam * float(...)`` in
# host Python while the fused graph summed in one jnp expression — a
# 1-ulp objective difference that could flip the iteration count when a
# tolerance landed exactly on a round's deviance delta.  All helpers are
# jnp-traceable (they vectorize over a config axis inside the selection
# scan) and exact for host floats.

def regularized_objective(dev, beta, lam, l1=0.0):
    """The convergence objective at beta: deviance + lam ||b||^2 (+ L1).

    ``beta`` may carry a leading config axis (objective per config); lam
    broadcasts (per-config lambda on the selection path).  Every driver
    forms its objective through this one expression so the stopping test
    below compares bit-identical floats across execution shapes.
    """
    beta = jnp.asarray(beta, jnp.float64)
    return (jnp.asarray(dev, jnp.float64)
            + lam * jnp.sum(beta**2, axis=-1)
            + 2.0 * l1 * jnp.sum(jnp.abs(beta), axis=-1))


def stop_threshold(obj, tol: float, num_parts: int, scale: float):
    """max(relative tolerance, fixed-point quantization floor).

    The deviance travels through the fixed-point codec, so no driver may
    test convergence tighter than the aggregate quantization of S
    institution deviances plus the revealed sum ((S+1) half-ulps at
    ``scale`` fractional resolution).
    """
    quant_floor = (num_parts + 1) * 0.5 / scale
    return jnp.maximum(tol * (1.0 + jnp.abs(obj)), quant_floor)


def should_stop(obj_prev, obj, tol: float, num_parts: int, scale: float):
    """True when |obj_prev - obj| clears the shared threshold."""
    return jnp.abs(obj_prev - obj) < stop_threshold(obj, tol, num_parts,
                                                    scale)


def stop_threshold_host(obj: float, tol: float, num_parts: int,
                        scale: float) -> float:
    """Pure-host twin of ``stop_threshold`` (IEEE-identical for floats).

    The per-round drivers test convergence on an objective that is
    ALREADY a host float (the round's one sync); routing it back through
    the jnp version cost a device round-trip per round for scalar
    arithmetic.  Same expression, same f64 semantics — a test pins the
    two bit-equal across a value grid including inf.
    """
    quant_floor = (num_parts + 1) * 0.5 / scale
    return max(tol * (1.0 + abs(obj)), quant_floor)


def should_stop_host(obj_prev: float, obj: float, tol: float,
                     num_parts: int, scale: float) -> bool:
    """Pure-host twin of ``should_stop`` for already-synced objectives."""
    return abs(obj_prev - obj) < stop_threshold_host(obj, tol, num_parts,
                                                     scale)


@dataclasses.dataclass
class FitResult:
    beta: np.ndarray
    iterations: int
    converged: bool
    deviance_trace: list
    # telemetry for Table 1 style reporting
    central_seconds: float = 0.0
    total_seconds: float = 0.0
    bytes_transmitted: int = 0


@dataclasses.dataclass
class RoundReport:
    """One secure round's audit record, shared by every driver.

    The first six fields are the per-round protocol telemetry; the
    trailing fault-supervision fields are filled in by
    ``runtime.supervisor.RoundSupervisor`` — an unsupervised round
    reports the fault-free defaults (no retries, no backoff, not
    degraded).
    """

    iteration: int
    responders: list
    stragglers: list
    centers_used: list
    objective: float
    bytes_transmitted: int
    retries: int = 0
    backoff_seconds: float = 0.0
    aborted_attempts: int = 0
    degraded: bool = False
    # PUBLIC in-graph metric leaves, piggybacked on the round's one
    # marked host sync (0.0 on paths that don't compute them in-graph)
    grad_norm: float = 0.0
    step_norm: float = 0.0


def newton_step(
    beta: jnp.ndarray,
    hessian: jnp.ndarray,
    gradient: jnp.ndarray,
    lam: float,
) -> jnp.ndarray:
    """Eq. 3: beta + (X^T W X + lam I)^{-1} (g - lam beta).

    This is the "securely derive beta_new" step (Algorithm 1, line 15)
    which operates on *revealed global aggregates* plus public
    lambda/beta.  The regularized Hessian X^T W X + lam I is SPD, so the
    solve is a Cholesky factorization: one code path on every platform,
    and the one XLA:TPU implements in float64 (its LU decomposition is
    float32-only).
    """
    d = beta.shape[0]
    A = hessian + lam * jnp.eye(d, dtype=hessian.dtype)
    rhs = gradient - lam * beta
    return beta + jax.scipy.linalg.cho_solve(
        jax.scipy.linalg.cho_factor(A), rhs
    )


def _soft_threshold(x, t):
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - t, 0.0)


def prox_newton_step(
    beta: jnp.ndarray,
    hessian: jnp.ndarray,
    gradient: jnp.ndarray,
    lam: float,
    l1: float,
    inner_steps: int = 200,
) -> jnp.ndarray:
    """Proximal Newton step for elastic-net logistic regression.

    The paper notes L1 support "is also possible" (Materials & Methods);
    crucially the *institution-side protocol is unchanged* — H_j and g_j
    are the same secret-shared summaries — only the Computation Centers'
    solver differs.  We minimize the local quadratic model

        m(b) = -g^T (b - beta) + 1/2 (b - beta)^T H (b - beta)
               + lam/2 ||b||^2 + l1 ||b||_1

    with FISTA (d x d problem, trivially cheap at the center; runs on
    *revealed global aggregates* only, like newton_step).  l1 = 0 reduces
    exactly to the L2 Newton step.
    """
    if l1 == 0.0:
        return newton_step(beta, hessian, gradient, lam)
    d = beta.shape[0]
    A = hessian + lam * jnp.eye(d, dtype=hessian.dtype)
    # Lipschitz constant of the quadratic part: A's largest eigenvalue
    # (A is symmetric, so this is its spectral norm)
    L = jnp.linalg.eigvalsh(A)[-1] + 1e-12
    # gradient of the smooth part at b: A (b - beta) - g + lam*beta
    #   (expand: H(b-beta) + lam*b - g ... careful) — derive:
    #   m_smooth(b) = -g^T(b-beta) + .5 (b-beta)^T H (b-beta) + lam/2 b^T b
    #   grad = -g + H (b - beta) + lam b

    def grad_smooth(b):
        return -gradient + hessian @ (b - beta) + lam * b

    def fista(carry, _):
        b, z, t = carry
        b_new = _soft_threshold(z - grad_smooth(z) / L, l1 / L)
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        z_new = b_new + ((t - 1.0) / t_new) * (b_new - b)
        return (b_new, z_new, t_new), None

    (b, _, _), _ = jax.lax.scan(
        fista, (beta, beta, jnp.asarray(1.0, beta.dtype)), None,
        length=inner_steps,
    )
    return b


def centralized_fit(
    X: jnp.ndarray,
    y: jnp.ndarray,
    lam: float = 1.0,
    tol: float = 1e-10,
    max_iter: int = 50,
) -> FitResult:
    """Gold-standard pooled IRLS (no privacy) for accuracy comparison."""
    d = X.shape[1]
    beta = jnp.zeros((d,), dtype=jnp.float64)
    dev_prev = np.inf
    trace: list[float] = []
    converged = False
    it = 0
    for it in range(1, max_iter + 1):
        s = local_summaries(beta, X, y)
        # regularized objective at the *current* beta (same ordering as the
        # secure protocol, where dev_j arrives with the summaries)
        obj = float(regularized_objective(s.deviance, beta, lam))
        trace.append(obj)
        if abs(dev_prev - obj) < tol * (1.0 + abs(obj)):
            converged = True
            break
        dev_prev = obj
        beta = newton_step(beta, s.hessian, s.gradient, lam)
    return FitResult(np.asarray(beta), it, converged, trace)


def _protected_tree(protect: str, hessian, gradient, dev):
    """The leaves Algorithm 1 secret-shares under a given protect mode."""
    tree = {}
    if protect in ("gradient", "both"):
        tree["gradient"] = gradient
    if protect in ("hessian", "both"):
        tree["hessian"] = hessian
    if protect != "none":
        tree["deviance"] = dev
    return tree


def _iteration_bytes(d: int, num_parts: int, protect: str,
                     agg: SecureCollective, include_count: bool = False,
                     num_live_centers: int | None = None,
                     num_configs: int = 1, extra_scalars: int = 0) -> int:
    """Per-iteration wire bytes (compat shim).

    The one static size model now lives on
    :meth:`repro.core.collective.SecureCollective.round_bytes`; this
    keeps the historical free-function signature working.
    """
    return agg.round_bytes(
        d, num_parts, protect, include_count=include_count,
        num_live_centers=num_live_centers, num_configs=num_configs,
        extra_scalars=extra_scalars,
    )


@functools.partial(
    jax.jit, static_argnames=("agg", "protect", "l1", "points",
                              "include_count", "summaries_backend")
)
def _fused_secure_iteration(beta, key, X, X32, y, counts, lam,
                            agg: SecureCollective, protect: str, l1: float,
                            points: tuple[int, ...] | None = None,
                            include_count: bool = False,
                            summaries_backend: str = "pallas"):
    """One whole secure Newton iteration as a single jitted graph.

    batched summaries -> batched protect (ONE encode+share launch over the
    S-leading flat buffers) -> single exact uint64 reduction over the
    institution axis (Algorithm 2) -> reveal of the *global* aggregate
    only -> prox/Newton update.  Returns ``(beta_new, objective,
    grad_norm, step_norm)``; the caller reads the three PUBLIC scalars
    back in the round's ONE host sync.  The metric leaves (||revealed
    global gradient||, ||beta_new - beta||) are ALWAYS computed — they
    derive from already-revealed aggregates, adding no declassification
    — so the graph is identical whether or not observability consumes
    them (the tracing-disabled bit-parity gate in
    ``benchmarks/obs_overhead.py`` relies on this).

    ``points``/``include_count``/``summaries_backend`` are the coordinator
    hooks: the fused ``StudyCoordinator.step`` reveals from its *live*
    centers' share slices (any >= t of the w points), mirrors the wire
    protocol's protected ``count`` leaf, and selects the summaries
    precision — "reference" (f64) for per-round parity with the loop
    oracle (the mid-run Newton transient amplifies Hessian perturbation
    ~10-40x, so f32-Gram backends hold only converged-beta parity),
    "pallas"/"mixed" for f32-Gram speed under that relaxed contract.
    """
    packed = PackedPartitions(X, X32, y, counts)
    sm = batched_local_summaries(
        beta, packed, backend=summaries_backend
    )
    hessian, gradient, dev = sm.hessian, sm.gradient, sm.deviance
    revealed = {}
    tree = _protected_tree(protect, hessian, gradient, dev)
    if tree and include_count:
        tree["count"] = counts.astype(jnp.float64)
    if tree:
        revealed = agg.secure_round_batched(key, tree, points=points)
    # unprotected leaves still only ever leave as cross-institution sums:
    # the annotated declassification the static taint gate certifies
    global_h = revealed["hessian"] if protect in ("hessian", "both") \
        else declassify_sum(hessian, axis=0)
    global_g = revealed["gradient"] if protect in ("gradient", "both") \
        else declassify_sum(gradient, axis=0)
    global_dev = revealed["deviance"] if protect != "none" \
        else declassify_sum(dev, axis=0)
    obj = regularized_objective(global_dev, beta, lam, l1)
    beta_new = prox_newton_step(
        beta, jnp.asarray(global_h, jnp.float64),
        jnp.asarray(global_g, jnp.float64), lam, l1,
    )
    grad_norm = jnp.linalg.norm(jnp.asarray(global_g, jnp.float64))
    step_norm = jnp.linalg.norm(beta_new - beta)
    return beta_new, obj, grad_norm, step_norm


class SecureFitDriver:
    """Stepwise Algorithm 1 with membership, liveness and crash-resume.

    ``secure_fit`` packs the whole fit into one call; this driver exposes
    the same computation round by round with the fault surface the
    deployment-shaped ``protocol.StudyCoordinator`` already has, so the
    ``runtime.supervisor.RoundSupervisor`` can drive all three secure
    drivers through one interface:

    * ``step()`` — one secure Newton round over the currently-responding
      institutions (online and under ``deadline``), revealed from the
      live centers' evaluation points.  An unrunnable round (fewer than
      ``min_responders`` institutions, fewer than t live centers) raises
      ``RuntimeError`` and leaves the fit state untouched, so a failed
      round can be retried or resumed cleanly.
    * ``state_dict()``/``load_state_dict()`` — a resumed driver replays
      BIT-identically (same rng stream, same trace floats) against an
      uninterrupted run: the coordinator-crash story.
    * liveness hooks — ``set_online``/``set_latency`` per institution
      name, ``set_center_online`` per evaluation point, and
      ``_midround_hooks`` (one-shot callables fired between protect and
      reveal) for center death inside a round: if >= t centers survive
      the round reveals from the survivors (bit-identical — any t-subset
      reconstructs exactly); below t it aborts with ``RuntimeError`` and
      the retry re-shares with fresh polynomials.

    A driver with every institution online, zero latencies and all
    centers live executes the exact ``secure_fit`` iteration sequence —
    same rng splits, same objective floats, same byte accounting — which
    is what lets ``secure_fit`` delegate here without disturbing its
    pinned parity tests.
    """

    def __init__(
        self,
        parts: Sequence[tuple[jnp.ndarray, jnp.ndarray]],
        lam: float = 1.0,
        tol: float = 1e-10,
        max_iter: int = 50,
        protect: str = "gradient",
        aggregator: SecureCollective | None = None,
        seed: int = 0,
        l1: float = 0.0,
        fused: bool | None = None,
        names: Sequence[str] | None = None,
        deadline: float | None = None,
        min_responders: int = 1,
        rounds: str = "step",
        rounds_per_sync: int | None = None,
        summaries_backend: str | None = None,
    ):
        if protect not in PROTECT_CHOICES:
            raise ValueError(f"protect must be one of {PROTECT_CHOICES}")
        self.agg = aggregator or SecureCollective()
        if fused is None:
            fused = self.agg.backend == "pallas"
        if fused and self.agg.backend != "pallas":
            raise ValueError(
                "fused secure_fit requires the pallas backend (the flat "
                "share buffers ARE its wire format); use fused=False with "
                "backend='reference'"
            )
        self.fused = fused
        if rounds not in ("step", "scan"):
            raise ValueError("rounds must be 'step' or 'scan'")
        if rounds == "scan" and not fused:
            raise ValueError(
                "rounds='scan' requires the fused pallas path (the scan "
                "body IS the fused iteration graph); use rounds='step' "
                "with fused=False for the loop oracle"
            )
        if rounds_per_sync is not None and rounds_per_sync < 1:
            raise ValueError("rounds_per_sync must be >= 1 (or None for "
                             "one scan block per fit)")
        self.rounds = rounds
        self.rounds_per_sync = rounds_per_sync
        # the fused iteration's summaries precision rung; None keeps the
        # historical fused-secure_fit default (the f32-Gram kernel rung,
        # converged-beta parity contract — see _fused_secure_iteration)
        if summaries_backend is None:
            summaries_backend = "pallas"
        if summaries_backend not in SUMMARY_BACKENDS:
            raise ValueError(
                f"summaries_backend must be one of {SUMMARY_BACKENDS}"
            )
        self.summaries_backend = summaries_backend
        self.parts = list(parts)
        self.names = (list(names) if names is not None
                      else [f"inst{j}" for j in range(len(self.parts))])
        if len(self.names) != len(self.parts):
            raise ValueError("names must match parts 1:1")
        self.lam = lam
        self.tol = tol
        self.max_iter = max_iter
        self.protect = protect
        self.l1 = float(l1)
        self.deadline = deadline
        self.min_responders = min_responders
        self.dim = self.parts[0][0].shape[1]
        self.online = [True] * len(self.parts)
        self.latency = [0.0] * len(self.parts)
        self.centers_online = [True] * self.agg.scheme.num_shares
        self._midround_hooks: list[Callable[[], None]] = []
        self.key = jax.random.PRNGKey(seed)
        self.beta = jnp.zeros((self.dim,), dtype=jnp.float64)
        # scan-mode rng slot counter: executed OR skipped scan slots both
        # advance it, so round r's in-graph fold is fold_in(key, r)
        # regardless of how the fit was cut into blocks (what makes
        # mid-scan resume bit-identical to an uninterrupted run)
        self._round_base = 0
        self.iteration = 0
        self.trace: list[float] = []
        self.reports: list[RoundReport] = []
        self._obj_prev = np.inf
        self.converged = False
        # (grad_norm, step_norm) from the last fused round's piggybacked
        # readback; None on the loop path (no in-graph metric leaves)
        self._last_round_metrics: tuple[float, float] | None = None
        self.central_seconds = 0.0
        self.total_seconds = 0.0
        self.bytes_transmitted = 0

    # -- liveness hooks (names mirror the supervisor's driver interface) ----
    def _idx(self, name: str) -> int:
        try:
            return self.names.index(name)
        except ValueError:
            raise KeyError(f"unknown institution {name!r}") from None

    def set_online(self, name: str, up: bool):
        self.online[self._idx(name)] = bool(up)

    def set_latency(self, name: str, latency: float):
        self.latency[self._idx(name)] = float(latency)

    def get_latency(self, name: str) -> float:
        return self.latency[self._idx(name)]

    def set_center_online(self, index: int, up: bool):
        if not (1 <= index <= len(self.centers_online)):
            raise ValueError(f"no center at evaluation point {index}")
        self.centers_online[index - 1] = bool(up)

    def cohort_indices(self) -> list[int]:
        """Current-round responders: online and under the deadline."""
        ok = [
            j for j in range(len(self.parts))
            if self.online[j]
            and (self.deadline is None or self.latency[j] <= self.deadline)
        ]
        if len(ok) < self.min_responders:
            raise RuntimeError(
                f"only {len(ok)} responders < min {self.min_responders}"
            )
        return ok

    def live_points(self) -> tuple[int, ...] | None:
        """Live centers' evaluation points (None when nothing is shared)."""
        if self.protect == "none":
            return None
        pts = tuple(
            i + 1 for i, up in enumerate(self.centers_online) if up
        )
        t = self.agg.scheme.threshold
        if len(pts) < t:
            raise RuntimeError(
                f"{len(pts)} centers < threshold {t}; "
                "aggregate unrecoverable this round"
            )
        return pts

    def _post_protect_points(self, points):
        """Re-check center liveness between protect and reveal.

        Fires the one-shot mid-round hooks (the chaos harness's
        center-death-inside-a-round events), then re-derives the reveal
        points from whoever is STILL online: >= t survivors reveal
        bit-identically; below t raises and the round aborts — the retry
        re-shares against fresh polynomials, so nothing about the aborted
        round's secrets is ever reconstructable.
        """
        hooks, self._midround_hooks = self._midround_hooks, []
        for h in hooks:
            h()
        if points is None:
            return None
        return self.live_points()

    # -- one Newton round ---------------------------------------------------
    @_traced("newton")
    def step(self) -> RoundReport:
        if self.rounds == "scan":
            # a supervised "round" in scan mode is one scan block: the
            # supervisor's retry re-enters at the failed block (a raise
            # below leaves ALL fit state unmutated, exactly like a failed
            # per-round step)
            reports = self.step_block()
            if reports:
                return reports[-1]
            if self.reports:  # stepped past convergence: nothing executed
                return self.reports[-1]
            raise RuntimeError("scan block executed no rounds")
        # validate the round BEFORE mutating any fit state: a failed round
        # must leave iteration/trace/beta untouched (rng advances only once
        # shares have actually been cut)
        cohort = self.cohort_indices()
        points = self.live_points()
        parts = [self.parts[j] for j in cohort]
        in_cohort = set(cohort)
        stragglers = [
            self.names[j] for j in range(len(self.parts))
            if self.online[j] and j not in in_cohort
        ]
        num_live = None if points is None else len(points)
        nbytes = self.agg.round_bytes(
            self.dim, len(parts), self.protect,
            num_live_centers=num_live,
        )
        if self.fused:
            obj, make_beta_new = self._round_fused(parts, points)
        else:
            obj, make_beta_new = self._round_loop(parts, points)
        # ---- the round is known-good: mutate state (mirrors
        #      StudyCoordinator._finish_round)
        self.iteration += 1
        self.trace.append(obj)
        self.bytes_transmitted += nbytes
        if should_stop_host(self._obj_prev, obj, self.tol, len(parts),
                            self.agg.codec.scale):
            self.converged = True
        else:
            self._obj_prev = obj
            self.beta = make_beta_new()
        gn, sn = self._last_round_metrics or (0.0, 0.0)
        report = RoundReport(
            self.iteration,
            [self.names[j] for j in cohort],
            stragglers,
            list(points or ()),
            obj,
            nbytes,
            grad_norm=gn,
            step_norm=sn,
        )
        self.reports.append(report)
        _metrics.observe_round(
            "secure_fit", nbytes, objective=obj,
            grad_norm=gn if self._last_round_metrics else None,
            step_norm=sn if self._last_round_metrics else None,
        )
        return report

    def _round_loop(self, parts, points):
        """The per-institution oracle walk (Algorithm 1 steps 3-16)."""
        self._last_round_metrics = None
        locals_: list[LocalSummaries] = [
            local_summaries(self.beta, Xj, yj) for Xj, yj in parts
        ]
        protected, plain = [], []
        for s in locals_:
            tree = _protected_tree(self.protect, s.hessian, s.gradient,
                                   s.deviance)
            self.key, sub = jax.random.split(self.key)
            protected.append(self.agg.protect(sub, tree) if tree else {})
            plain.append(
                {
                    k: v
                    for k, v in s._asdict().items()
                    if k not in tree and k != "count"
                }
            )

        # ---- centralized phase (Computation Centers, steps 11-16)
        t0 = time.perf_counter()
        revealed = {}
        if self.protect != "none":
            agg_protected = self.agg.aggregate(protected)
            pts = self._post_protect_points(points)
            if len(pts) < self.agg.scheme.num_shares:
                # non-contiguous survivor subset: slice the share axis to
                # the live points and reveal from them explicitly
                sel = jnp.asarray([p - 1 for p in pts])
                sliced = jax.tree_util.tree_map(
                    lambda sh: sh[sel], agg_protected
                )
                revealed = self.agg.reveal(sliced, points=list(pts))
            else:
                revealed = self.agg.reveal(agg_protected)
        else:
            self._post_protect_points(points)
        summed_plain = {
            k: sum(pl[k] for pl in plain) for k in plain[0]
        } if plain and plain[0] else {}
        global_h = revealed.get("hessian", summed_plain.get("hessian"))
        global_g = revealed.get("gradient", summed_plain.get("gradient"))
        global_dev = revealed.get("deviance", summed_plain.get("deviance"))
        # regularized objective at the current beta (summaries' beta) —
        # formed through the same expression as the fused graph so both
        # drivers compare bit-identical floats at the tolerance boundary
        obj = float(regularized_objective(global_dev, self.beta, self.lam,
                                          self.l1))
        self.central_seconds += time.perf_counter() - t0

        def make_beta_new():
            t1 = time.perf_counter()
            beta_new = prox_newton_step(
                self.beta,
                jnp.asarray(global_h, jnp.float64),
                jnp.asarray(global_g, jnp.float64),
                self.lam,
                self.l1,
            )
            self.central_seconds += time.perf_counter() - t1
            return beta_new

        return obj, make_beta_new

    def _round_fused(self, parts, points):
        """One fused jitted iteration (one dispatch + one host sync).

        X keeps the float64 payload: at protocol scale the f32-storage
        variant (``pack_partitions(..., dtype=jnp.float32)``, the TPU
        layout) lands right AT the fixed-point quantization boundary
        against the f64 loop path, while costing the same wall-clock here
        — the f64 gemvs are bandwidth-bound either way.  The pack is
        LRU-cached on the part buffers, so repeated rounds and
        straggler-shrunk cohorts don't re-pack.

        The fused graph has no host point between protect and reveal, so
        the mid-round hooks fire (and the reveal points re-derive) just
        before dispatch — an approximation that is exact for the revealed
        values, since reconstruction from any >= t points is the same
        field arithmetic wherever it happens.
        """
        packed = pack_partitions(parts)
        pts = self._post_protect_points(points)
        if pts is not None and len(pts) == self.agg.scheme.num_shares:
            # all centers live: the default first-t reveal secure_fit
            # always used (and the cache-friendliest static points value)
            pts = None
        self.key, sub = jax.random.split(self.key)
        beta_new, obj, grad_norm, step_norm = _fused_secure_iteration(
            self.beta, sub, packed.X, packed.X32, packed.y, packed.counts,
            self.lam, self.agg, self.protect, self.l1, points=pts,
            summaries_backend=self.summaries_backend,
        )
        # host-sync: the one readback per fused iteration — objective plus
        # the PUBLIC in-graph metric leaves, one transfer
        obj, grad_norm, step_norm = jax.device_get(
            (obj, grad_norm, step_norm)
        )
        self._last_round_metrics = (float(grad_norm), float(step_norm))
        return float(obj), lambda: beta_new

    # -- scan-resident blocks ------------------------------------------------
    @_traced("newton")
    def step_block(self, num_rounds: int | None = None
                   ) -> list[RoundReport]:
        """Up to ``num_rounds`` secure rounds as ONE ``lax.scan`` dispatch.

        The whole block — protect, Algorithm 2 aggregation, reveal and
        Newton update for every round, with the rng folded in-graph and
        convergence freezing the carry — runs as a single jitted graph;
        the only host sync is the block's (objective, active) trace
        readback, from which the per-round ``RoundReport`` records are
        reconstructed.  Default block length: ``rounds_per_sync``, or the
        fit's whole remaining ``max_iter`` budget (one sync per fit).

        The cohort and the live reveal points are frozen for the block
        (liveness is a host-side notion; the graph never re-enters
        Python), so supervision treats one block as one round: mid-round
        hooks fire before dispatch, a below-threshold cohort raises with
        ALL fit state unmutated, and the supervised retry re-enters at
        this block with the same rng slots.
        """
        if self.rounds != "scan":
            raise RuntimeError("step_block requires rounds='scan'")
        from .scanfit import fit_scan_block

        cohort = self.cohort_indices()
        points = self.live_points()
        parts = [self.parts[j] for j in cohort]
        in_cohort = set(cohort)
        stragglers = [
            self.names[j] for j in range(len(self.parts))
            if self.online[j] and j not in in_cohort
        ]
        num_live = None if points is None else len(points)
        nbytes = self.agg.round_bytes(
            self.dim, len(parts), self.protect,
            num_live_centers=num_live,
        )
        if num_rounds is None:
            num_rounds = self.rounds_per_sync or max(
                self.max_iter - self.iteration, 1
            )
        packed = pack_partitions(parts)
        pts = self._post_protect_points(points)
        if pts is not None and len(pts) == self.agg.scheme.num_shares:
            pts = None  # the all-live first-t default (cache-friendly)
        carry, objs, actives, gnorms, snorms = fit_scan_block(
            self.beta,
            jnp.asarray(self._obj_prev, jnp.float64),
            jnp.asarray(self.converged),
            jnp.zeros((), jnp.int32),
            self.key,
            jnp.asarray(self._round_base, jnp.int32),
            packed.X, packed.X32, packed.y, packed.counts, self.lam,
            agg=self.agg, protect=self.protect, l1=self.l1,
            tol=float(self.tol), points=pts, include_count=False,
            summaries_backend=self.summaries_backend,
            num_rounds=num_rounds, num_parts=len(parts),
            max_rounds=num_rounds,
        )
        # host-sync: the block's ONE readback — trace + metric leaves +
        # scalar carry in a single transfer (beta stays on device)
        objs, actives, gnorms, snorms, obj_prev_h, conv_h, base_h = \
            jax.device_get(
                (objs, actives, gnorms, snorms,
                 carry[1], carry[2], carry[4])
            )
        new_reports: list[RoundReport] = []
        for r in range(num_rounds):
            if not actives[r]:
                break
            self.iteration += 1
            self.trace.append(float(objs[r]))
            self.bytes_transmitted += nbytes
            report = RoundReport(
                self.iteration,
                [self.names[j] for j in cohort],
                stragglers,
                list(points or ()),
                float(objs[r]),
                nbytes,
                grad_norm=float(gnorms[r]),
                step_norm=float(snorms[r]),
            )
            self.reports.append(report)
            new_reports.append(report)
            _metrics.observe_round(
                "secure_fit_scan", nbytes, objective=report.objective,
                grad_norm=report.grad_norm, step_norm=report.step_norm,
            )
        self.beta = carry[0]
        self._obj_prev = float(obj_prev_h)
        self.converged = bool(conv_h)
        self._round_base = int(base_h)
        return new_reports

    def run(self, max_iter: int | None = None) -> FitResult:
        limit = self.max_iter if max_iter is None else max_iter
        t_total = time.perf_counter()
        while not self.converged and self.iteration < limit:
            if self.rounds == "scan":
                block = self.rounds_per_sync or (limit - self.iteration)
                self.step_block(min(block, limit - self.iteration))
            else:
                self.step()
        self.total_seconds += time.perf_counter() - t_total
        return self.result()

    def result(self) -> FitResult:
        # central_seconds stays 0.0 on the fused path: institution and
        # center phases live in one fused graph (the split remains
        # observable on the loop path and in protocol.StudyCoordinator)
        return FitResult(
            np.asarray(self.beta), self.iteration, self.converged,
            list(self.trace), central_seconds=self.central_seconds,
            total_seconds=self.total_seconds,
            bytes_transmitted=self.bytes_transmitted,
        )

    # -- checkpointing ------------------------------------------------------
    def state_dict(self) -> dict:
        """Everything needed to resume bit-identically after a crash."""
        return {
            "beta": np.asarray(self.beta),
            "iteration": np.asarray(self.iteration),
            "obj_prev": np.asarray(self._obj_prev),
            "trace": np.asarray(self.trace),
            "key": np.asarray(self.key),
            "converged": np.asarray(self.converged),
            "bytes": np.asarray(self.bytes_transmitted),
            "online": np.asarray(self.online),
            "latency": np.asarray(self.latency),
            "centers_online": np.asarray(self.centers_online),
            "round_base": np.asarray(self._round_base),
        }

    def load_state_dict(self, state: dict):
        self.beta = jnp.asarray(state["beta"])
        self.iteration = int(state["iteration"])
        self._obj_prev = float(state["obj_prev"])
        self.trace = [float(x) for x in state["trace"]]
        self.key = jnp.asarray(state["key"], dtype=jnp.uint32)
        self.converged = bool(state["converged"])
        self.bytes_transmitted = int(state.get("bytes", 0))
        if "online" in state:
            self.online = [bool(v) for v in state["online"]]
        if "latency" in state:
            self.latency = [float(v) for v in state["latency"]]
        if "centers_online" in state:
            self.centers_online = [bool(v) for v in state["centers_online"]]
        # pre-scan checkpoints: executed rounds and consumed rng slots
        # coincide in step mode, so iteration is the exact legacy value
        self._round_base = int(state.get("round_base", state["iteration"]))


def secure_fit(
    parts: Sequence[tuple[jnp.ndarray, jnp.ndarray]],
    lam: float = 1.0,
    tol: float = 1e-10,
    max_iter: int = 50,
    protect: str = "gradient",
    aggregator: SecureCollective | None = None,
    seed: int = 0,
    l1: float = 0.0,
    fused: bool | None = None,
    rounds: str = "step",
    rounds_per_sync: int | None = None,
    summaries_backend: str | None = None,
) -> FitResult:
    """Paper Algorithm 1 over S institutions' (X_j, y_j) partitions.

    ``protect`` selects the paper's pragmatic mode: known inference attacks
    need both H and g, so protecting either blocks them; "both" is the fully
    encrypted setting; "none" degrades to DataSHIELD-style plain exchange
    (the insecure baseline the paper improves on, kept for benchmarking).

    ``fused=None`` auto-selects: the pallas backend runs the jit-resident
    batched iteration (one kernel launch per phase, one host sync per
    iteration); the reference backend runs the per-institution Python loop
    (the oracle).  Pass ``fused=False`` to force the loop path on any
    backend — that is the pre-fusion baseline the e2e benchmark times.

    ``rounds="scan"`` runs the fit as scan-resident blocks of
    ``rounds_per_sync`` fused rounds (None: the WHOLE fit as one
    ``lax.scan`` — one host sync per fit); requires the fused path.

    This is the one-call form of ``SecureFitDriver`` (which adds stepwise
    execution, liveness hooks and ``state_dict`` crash-resume); a
    fault-free driver run is bit-identical to what this always produced.
    """
    driver = SecureFitDriver(
        parts, lam=lam, tol=tol, max_iter=max_iter, protect=protect,
        aggregator=aggregator, seed=seed, l1=l1, fused=fused,
        rounds=rounds, rounds_per_sync=rounds_per_sync,
        summaries_backend=summaries_backend,
    )
    return driver.run()
