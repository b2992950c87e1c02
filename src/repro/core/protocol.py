"""Algorithm 1 as an explicit multi-party protocol with failure handling.

`newton.secure_fit` is the compact in-process form; this module models the
*deployment* shape: Institution and ComputationCenter objects exchanging
messages through a coordinator, with the fault-tolerance features a
1000-node fleet needs:

* **Straggler mitigation** — each round has a deadline; institutions that
  miss it are excluded from that round's aggregate (the sums in Eqs. 4-6 are
  over whoever responded; the Newton iterate remains a valid ascent step on
  the responding cohort, and late institutions rejoin next round).
* **Center failure tolerance** — Shamir t-of-w: any t of the w centers can
  reconstruct, so up to w-t centers may be down in a round with zero effect
  on the result.
* **Elastic membership** — institutions may join/leave between rounds; the
  coordinator re-forms the cohort each iteration.
* **Checkpoint/restart** — protocol state (beta, iteration, deviance trace,
  rng) serializes to a dict for repro.checkpoint.

Timing is simulated (per-institution latency draws) so straggler logic is
deterministic and testable without wall-clock sleeps.

Two execution shapes for one round, selected by ``fused=``:

* **loop** (default) — the paper-shaped walk over Institution /
  ComputationCenter objects: one ``local_summaries`` + one protect
  dispatch per institution, explicit share slices at each center.  This
  is the oracle: bit-exact across secure-aggregation backends.
* **fused** — the cohort-level batched round (pallas backend only): the
  co-scheduled cohort's partitions pack ONCE (LRU-cached across churn)
  into the (S, N_max, d) layout, and the whole round — batched f64
  summaries, one encode+share launch over the S-leading flat buffers,
  single exact uint64 reduction (Algorithm 2), reveal from the *live*
  centers' slices, Newton update — runs as the same jitted graph
  ``secure_fit`` uses (``newton._fused_secure_iteration``).  Per-round
  betas match the loop oracle within fixed-point quantization; center
  dropout below threshold raises the identical ``RuntimeError``.
  ``summaries_backend="pallas"|"mixed"`` trades that per-round parity
  for f32-Gram speed (converged-beta parity only — the ``secure_fit``
  contract); see ``StudyCoordinator.__init__``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import metrics as _metrics
from ..obs.trace import traced as _traced
from .batched_summaries import (
    BACKENDS as SUMMARY_BACKENDS,
    pack_cache_evict,
    pack_partitions,
)
from .logreg import local_summaries
from .newton import (
    RoundReport,
    _fused_secure_iteration,
    newton_step,
    regularized_objective,
    should_stop_host,
)
from .collective import SecureCollective

__all__ = ["Institution", "ComputationCenter", "StudyCoordinator", "RoundReport"]


@dataclasses.dataclass
class Institution:
    """One data-holding party. Owns (X, y); never exports them."""

    name: str
    X: jnp.ndarray
    y: jnp.ndarray
    # simulated response latency (seconds) used for straggler decisions
    latency: float = 0.0
    online: bool = True

    def compute_and_protect(self, beta, protect: str, agg: SecureCollective,
                            key):
        s = local_summaries(beta, self.X, self.y)
        tree = {"deviance": s.deviance, "count": s.count.astype(jnp.float64)}
        if protect in ("gradient", "both"):
            tree["gradient"] = s.gradient
        if protect in ("hessian", "both"):
            tree["hessian"] = s.hessian
        shares = agg.protect(key, tree)
        plain = {}
        if protect in ("none", "gradient"):
            plain["hessian"] = s.hessian
        if protect in ("none", "hessian"):
            plain["gradient"] = s.gradient
        if protect == "none":
            plain["deviance"] = s.deviance
            plain["count"] = s.count.astype(jnp.float64)
            shares = {}
        return shares, plain


@dataclasses.dataclass
class ComputationCenter:
    """Holds one share slice of every protected submission."""

    index: int  # 1-based Shamir evaluation point
    online: bool = True
    _stash: list = dataclasses.field(default_factory=list)

    def receive(self, share_slice):
        self._stash.append(share_slice)

    @_traced("aggregate")
    def aggregate_local(self, field):
        """Algorithm 2 run at this center: share-wise sum of its slices.

        Streams a running uint64 accumulator over the stash (exact sum +
        single mod, fused by XLA) — no (S, ...) stack of submissions is
        allocated, so a center's memory high-water mark is one slice
        regardless of cohort size.
        """
        from .collective import _fold_sum_streaming

        if len(self._stash) == 1:
            return self._stash[0]
        acc = _fold_sum_streaming(tuple(self._stash), field, residue_axis=0)
        self._stash = [acc]
        return acc

    def clear(self):
        self._stash = []


# RoundReport now lives in .newton (it is shared by SecureFitDriver and the
# coordinator) and is re-exported here for the existing import surface.


# the result is cheap arithmetic; the small bound just avoids pinning
# every aggregator config a long-lived process ever constructs
@functools.lru_cache(maxsize=64)
def _round_bytes(d: int, cohort_size: int, protect: str,
                 agg: SecureCollective, num_live_centers: int) -> int:
    """Per-round wire bytes from static shapes/dtypes alone.

    Every round moves the same messages for a given (cohort size, protect
    mode, scheme) — the summary shapes never change — so the telemetry
    needs no per-leaf walk inside the round.  Delegates to the one
    ``SecureCollective.round_bytes`` size model with the coordinator wire
    protocol's two deltas: the protected tree carries the extra ``count``
    leaf, and each online center receives a 1/w slice of the share
    buffer (uint32 flat tiles on pallas, uint64 leaf tensors on
    reference).  ``tests/test_protocol.py`` pins this formula against a
    per-leaf walk of the actual messages.
    """
    return agg.round_bytes(
        d, cohort_size, protect, include_count=True,
        num_live_centers=num_live_centers,
    )


class StudyCoordinator:
    """Drives Algorithm 1 across institutions + centers, fault-tolerantly."""

    def __init__(
        self,
        institutions: Sequence[Institution],
        lam: float = 1.0,
        protect: str = "gradient",
        aggregator: SecureCollective | None = None,
        num_centers: int | None = None,
        deadline: float | None = None,
        min_responders: int = 1,
        tol: float = 1e-10,
        seed: int = 0,
        fused: bool = False,
        summaries_backend: str | None = None,
        rounds: str = "step",
        rounds_per_sync: int | None = None,
    ):
        self.institutions = list(institutions)
        self.lam = lam
        self.protect = protect
        self.agg = aggregator or SecureCollective()
        # fused rounds need the pallas flat-buffer wire format; the loop
        # stays the default because it is the bit-exact backend oracle
        if fused and self.agg.backend != "pallas":
            raise ValueError(
                "fused coordinator rounds require the pallas backend (the "
                "flat share buffers ARE the batched wire format); use "
                "fused=False with backend='reference'"
            )
        self.fused = fused
        if rounds not in ("step", "scan"):
            raise ValueError("rounds must be 'step' or 'scan'")
        if rounds == "scan" and not fused:
            raise ValueError(
                "rounds='scan' requires fused=True (the scan body IS the "
                "fused cohort round); the loop path stays per-round"
            )
        if rounds_per_sync is not None and rounds_per_sync < 1:
            raise ValueError("rounds_per_sync must be >= 1 (or None for "
                             "one scan block per run)")
        self.rounds = rounds
        self.rounds_per_sync = rounds_per_sync
        # Precision ladder for the fused round's batched summaries.
        # "reference" (default) — f64, per-ROUND beta parity with the loop
        # oracle at the f64 rounding floor (well inside fixed-point
        # quantization); the coordinator's contract.  "pallas" / "mixed" —
        # the f32-Gram kernel layouts (TPU dtype / split-accumulation):
        # measurably faster at production N, but the mid-run Newton
        # transient amplifies the f32 Hessian perturbation ~10-40x, so
        # only the CONVERGED beta (fixed by the f64 gradient, not H) is
        # guaranteed within quantization — the same relaxed contract the
        # fused ``secure_fit`` ships with.
        if summaries_backend is None:
            summaries_backend = "reference"
        if summaries_backend not in SUMMARY_BACKENDS:
            raise ValueError(
                f"summaries_backend must be one of {SUMMARY_BACKENDS}"
            )
        self.summaries_backend = summaries_backend
        # Fewer centers than shares is allowed: the scheme's remaining
        # evaluation points stay FREE, and ``provision_center`` can bring a
        # replacement up at one of them after a center failure (a fresh
        # point's share slice was never sent to the failed node).  More
        # centers than shares is impossible — there is no share to give
        # them — and fewer than t can never reconstruct.
        w = self.agg.scheme.num_shares
        n_centers = w if num_centers is None else num_centers
        if not (self.agg.scheme.threshold <= n_centers <= w):
            raise ValueError(
                f"num_centers must lie in [threshold={self.agg.scheme.threshold}, "
                f"num_shares={w}] (points beyond num_centers stay free for "
                "re-provisioning)"
            )
        self.centers = [ComputationCenter(i + 1) for i in range(n_centers)]
        # one-shot callables fired between protect and reveal of the next
        # round — the chaos harness's center-death-inside-a-round events
        self._midround_hooks: list[Callable[[], None]] = []
        self.deadline = deadline
        self.min_responders = min_responders
        self.tol = tol
        self.key = jax.random.PRNGKey(seed)
        d = self.institutions[0].X.shape[1]
        self.beta = jnp.zeros((d,), dtype=jnp.float64)
        # scan-mode rng slot counter (executed or skipped slots both
        # advance it — see core.scanfit): checkpointed for mid-scan resume
        self._round_base = 0
        self.iteration = 0
        self.trace: list[float] = []
        self.reports: list[RoundReport] = []
        self._obj_prev = np.inf
        self.converged = False
        # (grad_norm, step_norm) from the last fused round's piggybacked
        # readback; None on the loop path (no in-graph metric leaves)
        self._last_round_metrics: tuple[float, float] | None = None

    # -- fault/elasticity hooks ----------------------------------------------
    def cohort(self) -> list[Institution]:
        """Current-round responders: online and under the deadline."""
        live = [i for i in self.institutions if i.online]
        if self.deadline is not None:
            ok = [i for i in live if i.latency <= self.deadline]
        else:
            ok = live
        if len(ok) < self.min_responders:
            raise RuntimeError(
                f"only {len(ok)} responders < min {self.min_responders}"
            )
        return ok

    def live_centers(self) -> list[ComputationCenter]:
        up = [c for c in self.centers if c.online]
        if len(up) < self.agg.scheme.threshold:
            raise RuntimeError(
                f"{len(up)} centers < threshold {self.agg.scheme.threshold}; "
                "aggregate unrecoverable this round"
            )
        return up

    def add_institution(self, inst: Institution):
        # churn invalidation: no later cohort may reuse a padded batch
        # built around this institution's buffer ids.  Belt-and-braces on
        # top of the cache's identity keys + evict-on-collect weakrefs —
        # it trades a repack of the churned cohort (packs without this
        # institution stay resident) for making stale reuse structurally
        # impossible even if a caller mutates non-jax buffers in place.
        pack_cache_evict([(inst.X, inst.y)])
        self.institutions.append(inst)

    def remove_institution(self, name: str):
        gone = [i for i in self.institutions if i.name == name]
        self.institutions = [i for i in self.institutions if i.name != name]
        pack_cache_evict([(i.X, i.y) for i in gone])

    def provision_center(self, index: int | None = None) -> ComputationCenter:
        """Bring up a replacement/additional Computation Center.

        With no ``index``, prefer a FRESH evaluation point — one of the
        scheme's points in 1..w not currently assigned to any center —
        since a fresh point's share slice was never distributed to the
        failed node; fall back to replacing the lowest-indexed dead
        center in place.  Replacing at an old point is still safe:
        every round shares fresh polynomials, so a replacement center
        learns nothing about earlier rounds' secrets, and
        ``SecureCollective._validated_points`` guards every reveal
        against duplicate/out-of-range points.  The next round's shares
        are simply cut against the new point set.
        """
        w = self.agg.scheme.num_shares
        used = {c.index for c in self.centers}
        if index is None:
            free = [p for p in range(1, w + 1) if p not in used]
            if free:
                index = free[0]
            else:
                dead = [c.index for c in self.centers if not c.online]
                if not dead:
                    raise RuntimeError(
                        "no free evaluation point and no dead center to "
                        "replace"
                    )
                index = min(dead)
        if not (1 <= index <= w):
            raise ValueError(f"evaluation point must be in 1..{w}")
        fresh = ComputationCenter(index)
        if index in used:
            old = next(c for c in self.centers if c.index == index)
            if old.online:
                raise RuntimeError(
                    f"center at point {index} is still online; refusing to "
                    "replace it"
                )
            self.centers[self.centers.index(old)] = fresh
        else:
            self.centers.append(fresh)
            self.centers.sort(key=lambda c: c.index)
        return fresh

    def _fire_midround_hooks(self):
        hooks, self._midround_hooks = self._midround_hooks, []
        for h in hooks:
            h()

    # -- one Newton round ------------------------------------------------------
    @_traced("newton")
    def step(self, fused: bool | None = None) -> RoundReport:
        """One secure Newton round.  ``fused=None`` uses the constructor
        setting; an explicit value overrides it for this round only (the
        two shapes interleave freely: round state is just beta/rng)."""
        use_fused = self.fused if fused is None else fused
        if use_fused and self.agg.backend != "pallas":
            raise ValueError(
                "fused coordinator rounds require the pallas backend"
            )
        if self.rounds == "scan" and use_fused:
            # a supervised "round" in scan mode is one scan block; a raise
            # inside leaves all round state unmutated, so retries re-enter
            # at the failed block exactly like a failed per-round step
            reports = self.step_block()
            if reports:
                return reports[-1]
            if self.reports:  # stepped past convergence
                return self.reports[-1]
            raise RuntimeError("scan block executed no rounds")
        # Validate the round BEFORE mutating any state: a round that cannot
        # run (below quorum, below center threshold) must leave
        # iteration/trace/beta exactly as they were, so a supervised retry
        # or a state_dict resume replays cleanly (the counter used to
        # advance first, making every failed round an off-by-one in the
        # resumed trace).
        cohort = self.cohort()
        if self.protect != "none":
            self.live_centers()
        stragglers = [
            i.name for i in self.institutions
            if i.online and i not in cohort
        ]
        # bytes are accounted at protect time: a center that dies between
        # protect and reveal already received its slice this round
        num_live = sum(1 for c in self.centers if c.online)
        nbytes = _round_bytes(
            cohort[0].X.shape[1], len(cohort), self.protect, self.agg,
            num_live,
        )
        if use_fused:
            obj, make_beta_new = self._round_fused(cohort)
        else:
            obj, make_beta_new = self._round_loop(cohort)
        return self._finish_round(
            obj, make_beta_new, cohort, stragglers, nbytes
        )

    def _round_loop(self, cohort):
        """The per-institution oracle walk (paper-shaped deployment)."""
        self._last_round_metrics = None
        for c in self.centers:
            c.clear()
        plains = []
        submissions = []
        for inst in cohort:
            self.key, sub = jax.random.split(self.key)
            shares, plain = inst.compute_and_protect(
                self.beta, self.protect, self.agg, sub
            )
            plains.append(plain)
            if shares:
                submissions.append(shares)
                for center in self.centers:
                    if not center.online:
                        continue  # lost share slice; t-of-w absorbs it
                    # slice by the center's own evaluation point, not its
                    # list position: after re-provisioning the point set
                    # may be non-contiguous
                    center.receive(jax.tree_util.tree_map(
                        lambda s, i=center.index - 1: s[i], shares
                    ))

        # center death BETWEEN protect and reveal lands here: the one-shot
        # mid-round hooks flip liveness after the slices were distributed,
        # and live_centers() below reveals from the survivors (>= t is
        # bit-identical — any t-subset reconstructs exactly) or raises and
        # aborts the round; the retry re-shares with fresh polynomials
        self._fire_midround_hooks()

        # centers run Algorithm 2 share-wise — each stacks its S received
        # slices and reduces them in one fused pass (exact in the field,
        # so bit-identical to sequential accumulation) — then >= t of
        # them jointly reconstruct the global aggregate only
        revealed = {}
        if self.protect != "none" and submissions:
            up = self.live_centers()
            agg_slices = [c.aggregate_local(self.agg.scheme.field) for c in up]
            points = [c.index for c in up]
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs, axis=0), *agg_slices
            )
            revealed = self.agg.reveal(stacked, points=points)

        plain_sum = {
            k: sum(pl[k] for pl in plains) for k in plains[0]
        } if plains and plains[0] else {}
        merged = {**plain_sum, **revealed}
        H = jnp.asarray(merged["hessian"], jnp.float64)
        g = jnp.asarray(merged["gradient"], jnp.float64)
        # same objective expression as the fused graph: the loop and fused
        # drivers must compare bit-identical floats in the stopping rule
        obj = float(regularized_objective(
            merged["deviance"], self.beta, self.lam
        ))
        return obj, lambda: newton_step(self.beta, H, g, self.lam)

    def _round_fused(self, cohort):
        """Cohort-level batched round: one jitted graph, one host sync.

        The co-scheduled cohort's partitions pack once into the
        (S, N_max, d) layout, LRU-cached on the part buffers: repeated
        rounds and straggler-shrunk cohorts hit the cache; packs
        containing a churned (added/removed) institution are invalidated
        by the membership hooks and rebuilt on next use.  The whole
        round runs as
        the fused ``secure_fit`` iteration with the coordinator's wire
        tree (deviance + count + protected summaries) revealed from the
        LIVE centers' share slices.  A cohort below the center threshold
        raises the same ``RuntimeError`` as the loop path — never a
        reduction over a short share axis.  ``summaries_backend`` picks
        the precision contract (see ``__init__``).
        """
        # the fused graph has no host point between protect and reveal, so
        # the mid-round death hooks fire before dispatch and the reveal
        # points are derived from the survivors — exact for the revealed
        # values (any >= t points reconstruct identically), and the same
        # abort semantics as the loop path below threshold
        self._fire_midround_hooks()
        if self.protect != "none":
            # identical failure semantics to the loop path, checked
            # BEFORE any computation so a dropped center can't be
            # silently absorbed by revealing from a default prefix
            points = tuple(c.index for c in self.live_centers())
        else:
            points = None
        packed = pack_partitions([(i.X, i.y) for i in cohort])
        self.key, sub = jax.random.split(self.key)
        beta_new, obj, grad_norm, step_norm = _fused_secure_iteration(
            self.beta, sub, packed.X, packed.X32, packed.y, packed.counts,
            self.lam, self.agg, self.protect, 0.0,
            points=points, include_count=True,
            summaries_backend=self.summaries_backend,
        )
        # host-sync: the round's one readback (secure_fit's twin) —
        # objective plus the PUBLIC in-graph metric leaves, one transfer
        obj, grad_norm, step_norm = jax.device_get(
            (obj, grad_norm, step_norm)
        )
        self._last_round_metrics = (float(grad_norm), float(step_norm))
        return float(obj), lambda: beta_new

    # -- scan-resident blocks --------------------------------------------------
    @_traced("newton")
    def step_block(self, num_rounds: int | None = None
                   ) -> list[RoundReport]:
        """Up to ``num_rounds`` fused cohort rounds as ONE ``lax.scan``.

        The deployment-shaped twin of ``SecureFitDriver.step_block``: the
        whole block runs as a single jitted graph (in-graph rng folds,
        ``should_stop``-driven freeze), with one host sync — the block's
        trace readback — from which the per-round ``RoundReport`` records
        are rebuilt through the same ``_finish_round`` bookkeeping the
        per-round paths use.  The cohort and live centers are frozen for
        the block; mid-round death hooks fire before dispatch (the fused
        path's usual approximation — exact for the revealed values) and a
        below-threshold block raises with all round state unmutated.
        Default block length: ``rounds_per_sync``, or the remaining
        ``run()`` budget (one sync per study).
        """
        if self.rounds != "scan":
            raise RuntimeError("step_block requires rounds='scan'")
        from .scanfit import fit_scan_block

        cohort = self.cohort()
        if self.protect != "none":
            self.live_centers()
        stragglers = [
            i.name for i in self.institutions
            if i.online and i not in cohort
        ]
        num_live = sum(1 for c in self.centers if c.online)
        d = cohort[0].X.shape[1]
        nbytes = _round_bytes(d, len(cohort), self.protect, self.agg,
                              num_live)
        if num_rounds is None:
            # 50 is run()'s default max_iter — the whole-study budget
            num_rounds = self.rounds_per_sync or max(50 - self.iteration, 1)
        self._fire_midround_hooks()
        if self.protect != "none":
            points = tuple(c.index for c in self.live_centers())
        else:
            points = None
        packed = pack_partitions([(i.X, i.y) for i in cohort])
        carry, objs, actives, gnorms, snorms = fit_scan_block(
            self.beta,
            jnp.asarray(self._obj_prev, jnp.float64),
            jnp.asarray(self.converged),
            jnp.zeros((), jnp.int32),
            self.key,
            jnp.asarray(self._round_base, jnp.int32),
            packed.X, packed.X32, packed.y, packed.counts, self.lam,
            agg=self.agg, protect=self.protect, l1=0.0,
            tol=float(self.tol), points=points, include_count=True,
            summaries_backend=self.summaries_backend,
            num_rounds=num_rounds, num_parts=len(cohort),
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
            new_reports.append(RoundReport(
                self.iteration,
                [i.name for i in cohort],
                stragglers,
                [c.index for c in self.centers if c.online],
                float(objs[r]),
                nbytes,
                grad_norm=float(gnorms[r]),
                step_norm=float(snorms[r]),
            ))
            self.reports.append(new_reports[-1])
            _metrics.observe_round(
                "coordinator_scan", nbytes,
                objective=float(objs[r]),
                grad_norm=float(gnorms[r]), step_norm=float(snorms[r]),
            )
        self.beta = carry[0]
        self._obj_prev = float(obj_prev_h)
        self.converged = bool(conv_h)
        self._round_base = int(base_h)
        return new_reports

    def _finish_round(self, obj, make_beta_new, cohort, stragglers,
                      nbytes) -> RoundReport:
        """Convergence bookkeeping shared verbatim by both round shapes.

        The ONLY place round state mutates: a raise anywhere earlier in
        ``step`` leaves the coordinator exactly as it was.
        """
        self.iteration += 1
        self.trace.append(obj)
        if should_stop_host(self._obj_prev, obj, self.tol, len(cohort),
                            self.agg.codec.scale):
            self.converged = True
        else:
            self._obj_prev = obj
            self.beta = make_beta_new()
        gn, sn = self._last_round_metrics or (0.0, 0.0)
        report = RoundReport(
            self.iteration,
            [i.name for i in cohort],
            stragglers,
            [c.index for c in self.centers if c.online],
            obj,
            nbytes,
            grad_norm=gn,
            step_norm=sn,
        )
        self.reports.append(report)
        _metrics.observe_round(
            "coordinator", nbytes, objective=obj,
            grad_norm=gn if self._last_round_metrics else None,
            step_norm=sn if self._last_round_metrics else None,
        )
        return report

    def run(self, max_iter: int = 50) -> np.ndarray:
        while not self.converged and self.iteration < max_iter:
            if self.rounds == "scan" and self.fused:
                block = self.rounds_per_sync or (max_iter - self.iteration)
                self.step_block(min(block, max_iter - self.iteration))
            else:
                self.step()
        return np.asarray(self.beta)

    # -- checkpointing ----------------------------------------------------------
    def state_dict(self) -> dict:
        return {
            "beta": np.asarray(self.beta),
            "iteration": np.asarray(self.iteration),
            "obj_prev": np.asarray(self._obj_prev),
            "trace": np.asarray(self.trace),
            "key": np.asarray(self.key),
            "converged": np.asarray(self.converged),
            "round_base": np.asarray(self._round_base),
        }

    def load_state_dict(self, state: dict):
        self.beta = jnp.asarray(state["beta"])
        self.iteration = int(state["iteration"])
        self._obj_prev = float(state["obj_prev"])
        self.trace = [float(x) for x in state["trace"]]
        self.key = jnp.asarray(state["key"], dtype=jnp.uint32)
        self.converged = bool(state["converged"])
        # pre-scan checkpoints: slots == executed rounds in step mode
        self._round_base = int(state.get("round_base", state["iteration"]))
