"""Traffic kind ``cv_path``: one analyst, a closed loop of secure
cross-validated lambda paths.

Each job builds a new ``SelectionCoordinator`` over the resident sites
and runs its whole path (``run_path``): the traffic's lambda grid in
descending order, ``folds``-fold cross-validation with warm starts, the
1-SE pick from the revealed held-out deviance, and the refit over all
rows at the pick.  The job's fold seed is drawn from the run's seed, so
every job holds out other rows.

The warm job runs a path of the grid's first lambda alone: the same
sweep and refit programs, at a fraction of a whole path's rounds.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from .. import data, reference, work
from . import single_fit

__all__ = ["Answer", "Cell"]

HELD_OUT_SCALARS = 3  # held-out deviance, correct count, row count


@dataclasses.dataclass
class Answer:
    """What one path returned to the analyst."""

    fold_seed: int
    lambdas: np.ndarray  # (L,) descending
    fold_betas: np.ndarray  # (L, K, d) converged fold fits
    fold_converged: np.ndarray  # (L, K)
    fold_rounds: np.ndarray  # (L, K)
    val_deviance: np.ndarray  # (L, K) revealed held-out -2 log L
    val_count: np.ndarray  # (L, K) revealed held-out rows
    pick: int  # index of the 1-SE lambda
    beta: np.ndarray  # (d,) the refit at the pick
    refit_rounds: int
    rounds_total: int  # rounds the path's launches ran, the refit's too


class Cell(single_fit.Cell):
    """``config`` and ``traffic`` as loaded from their files."""

    def warm(self):
        self._path(int(self.rng.integers(2**31)),
                   [float(self.traffic["lambdas"][0])])

    def jobs(self):
        """The endless job stream: a fold seed for each path."""
        while True:
            yield int(self.rng.integers(2**31))

    def _path(self, fold_seed: int, lambdas):
        from repro.selection import SelectionCoordinator

        cfg, tr = self.config, self.traffic
        coord = SelectionCoordinator(
            self.sites, lambdas, num_folds=int(tr["folds"]),
            l1=float(tr["l1"]), protect=cfg["protect"], aggregator=self.agg,
            tol=cfg["tol"], seed=int(self.rng.integers(2**31)),
            fold_seed=fold_seed, summaries_backend=cfg["summaries"],
            lam_block=int(tr["lam_block"]),
            rounds_per_sync=int(tr["rounds_per_sync"]),
            max_rounds=cfg["max_rounds"],
        )
        return coord.run_path()

    def run_job(self, fold_seed: int) -> Answer:
        rep = self._path(fold_seed, [float(v) for v in
                                     self.traffic["lambdas"]])
        return Answer(fold_seed, np.asarray(rep.lambdas, np.float64),
                      np.asarray(rep.fold_betas, np.float64),
                      np.asarray(rep.fold_converged, bool),
                      np.asarray(rep.fold_rounds),
                      np.asarray(rep.val_deviance, np.float64),
                      np.asarray(rep.val_count, np.float64),
                      int(rep.one_se_index),
                      np.asarray(rep.beta, np.float64),
                      int(rep.refit_rounds), int(rep.rounds_total))

    # -- accounting -----------------------------------------------------------
    def job_flops(self, answer: Answer) -> float:
        """Useful operations of the path: each fold fit's rounds over its
        training rows (its held-out rows' linear predictor besides), the
        refit's over all rows."""
        n, d = self.config["rows"], self.config["features"]
        trained = n - answer.val_count
        per = (2.0 * trained * d * d + 2.0 * n * d + 2.0 * trained * d
               + d ** 3 / 3.0)
        return float(np.sum(answer.fold_rounds * per)
                     + answer.refit_rounds * work.fit_round_flops(n, d))

    def kernel_work(self, answers) -> dict:
        """The least work of the named kernels over ``answers``: one
        launch of each a round; a sweep round carries ``folds``
        configurations, a refit round one."""
        cfg, sh = self.config, self.config["shamir"]
        n, s, d = cfg["rows"], cfg["sites"], cfg["features"]
        k = int(self.traffic["folds"])
        flops = nbytes = shamir = 0.0
        for a in answers:
            sweep = a.rounds_total - a.refit_rounds
            for configs, held, rounds in ((k, n, sweep),
                                          (1, 0, a.refit_rounds)):
                f, b = work.irls_cv_kernel(n, s, d, configs, held)
                flops += rounds * f
                nbytes += rounds * b
                shamir += rounds * (
                    work.share_kernel(s, d, cfg["protect"], sh["residues"],
                                      sh["threshold"], sh["centers"],
                                      configs, HELD_OUT_SCALARS)
                    + work.reconstruct_kernel(d, cfg["protect"],
                                              sh["residues"], sh["threshold"],
                                              configs, HELD_OUT_SCALARS))
        return {"irls_cv": (flops, nbytes), "shamir": shamir}

    # -- the comparison with the plain reference ------------------------------
    def _folds(self, fold_seed: int, parts) -> list[np.ndarray]:
        k = int(self.traffic["folds"])
        return [data.fold_ids(int(X.shape[0]), k, f"site{j}", fold_seed)
                for j, (X, _) in enumerate(parts)]

    def _split(self, parts_ref, folds, fold: int):
        """(training rows, held-out rows) of ``fold``, pooled."""
        tr, va = [], []
        for (X, y), f in zip(parts_ref, folds):
            keep = np.flatnonzero(f != fold)
            out = np.flatnonzero(f == fold)
            tr.append((X[keep], y[keep]))
            va.append((X[out], y[out]))
        return reference.pool(tr), reference.pool(va)

    def checked(self, answers):
        """(job, [(lambda index, fold)]) pairs compared: ``check_jobs``
        jobs and ``check_fits`` fold fits of each, drawn from the seed."""
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 2]))
        L = len(self.traffic["lambdas"])
        k = int(self.traffic["folds"])
        n_jobs = min(int(self.traffic["check_jobs"]), len(answers))
        jobs = sorted(rng.choice(len(answers), size=n_jobs, replace=False))
        n_fits = min(int(self.traffic["check_fits"]), L * k)
        out = []
        for j in jobs:
            cells = rng.choice(L * k, size=n_fits, replace=False)
            out.append((answers[j], [(int(c) // k, int(c) % k)
                                     for c in sorted(cells)]))
        return out

    def compare(self, answers, parts_ref, fits=None) -> dict:
        """The worst of each compared number over the checked jobs.

        The reference fits each checked (lambda, fold) on the fold's
        training rows, and the refit's lambda on all rows, from zero in
        float64 to its stopping rule and two Newton steps past it:

        * ``fold_beta_gap``: a fold fit's converged beta against the
          reference's, max-norm over the reference's max-norm (the CV
          summaries kernel, protect/reveal, the solve, the warm start);
        * ``val_deviance_gap``: the revealed held-out deviance against the
          reference's held-out deviance at the fold fit's beta, relative
          (held-out rows through the kernel and the reveal);
        * ``val_count_gap``: revealed held-out rows against the fold's
          (the fold assignment), exact;
        * ``refit_beta_gap``: the refit against the reference's fit over
          all rows at the picked lambda;
        * ``pick_gap``: the picked lambda's index against the 1-SE rule
          applied to the revealed held-out deviances, exact;
        * ``unconverged``: fold fits and refits that never converged.

        ``fits`` replaces the sampled (lambda index, fold) pairs for
        every job (the calibration compares more of them).
        """
        cfg = self.config
        out = {"fold_beta_gap": 0.0, "val_deviance_gap": 0.0,
               "val_count_gap": 0.0, "refit_beta_gap": 0.0,
               "pick_gap": 0.0, "unconverged": 0.0}
        pairs = self.checked(answers)
        if fits is not None:
            pairs = [(a, fits) for a, _ in pairs]
        # the folds on the program's device: the same sort breaks ties
        folds = {a.fold_seed: self._folds(a.fold_seed, parts_ref)
                 for a, _ in pairs}
        cpu = jax.devices("cpu")[0]
        with jax.default_device(cpu):
            whole = reference.pool(parts_ref)
            for a, cells in pairs:
                folds_a = folds[a.fold_seed]
                for li, f in cells:
                    tr, va = self._split(parts_ref, folds_a, f)
                    ref = _converged(tr, a.lambdas[li], cfg)
                    out["fold_beta_gap"] = max(out["fold_beta_gap"],
                                               _gap(a.fold_betas[li, f], ref))
                    dev = reference.deviance(a.fold_betas[li, f], [va])
                    out["val_deviance_gap"] = max(
                        out["val_deviance_gap"],
                        abs(a.val_deviance[li, f] - dev) / abs(dev))
                    out["val_count_gap"] = max(
                        out["val_count_gap"],
                        abs(a.val_count[li, f] - float(va[1].shape[0])))
                ref = _converged(whole, a.lambdas[a.pick], cfg)
                out["refit_beta_gap"] = max(out["refit_beta_gap"],
                                            _gap(a.beta, ref))
                out["pick_gap"] = max(out["pick_gap"], float(abs(
                    a.pick - one_se_index(a.val_deviance, a.val_count))))
                out["unconverged"] = max(out["unconverged"], float(
                    np.sum(~a.fold_converged)
                    + (a.refit_rounds >= cfg["max_rounds"])))
        out["jobs_compared"] = float(len(pairs))
        return out

    def control_answers(self, fold_seed, parts_dev, prec) -> Answer:
        """The reference in the program's place at precision ``prec``: a
        whole path, each fold fit from zero."""
        cfg = self.config
        k = int(self.traffic["folds"])
        lams = np.asarray(sorted((float(v) for v in self.traffic["lambdas"]),
                                 reverse=True))
        folds = self._folds(fold_seed, parts_dev)
        L, d = len(lams), cfg["features"]
        betas = np.zeros((L, k, d))
        conv = np.zeros((L, k), bool)
        rounds = np.zeros((L, k), np.int32)
        vdev = np.zeros((L, k))
        vcnt = np.zeros((L, k))
        for f in range(k):
            tr, va = self._split(parts_dev, folds, f)
            for li, lam in enumerate(lams):
                t = reference.fit([tr], lam, prec, tol=cfg["tol"],
                                  max_rounds=cfg["max_rounds"])
                betas[li, f], conv[li, f] = t.beta, t.converged
                rounds[li, f] = t.rounds
                vdev[li, f] = reference.deviance(t.beta, [va], prec.terms)
                vcnt[li, f] = va[1].shape[0]
        pick = one_se_index(vdev, vcnt)
        t = reference.fit([reference.pool(parts_dev)], lams[pick], prec,
                          tol=cfg["tol"], max_rounds=cfg["max_rounds"])
        return Answer(fold_seed, lams, betas, conv, rounds, vdev, vcnt, pick,
                      t.beta, t.rounds, int(rounds.sum()) + t.rounds)


def one_se_index(val_deviance, val_count) -> int:
    """The largest lambda (first index of the descending grid) whose mean
    held-out deviance per row is within one standard error, over folds,
    of the least."""
    per = np.asarray(val_deviance) / np.maximum(np.asarray(val_count), 1.0)
    mean = per.mean(axis=1)
    se = per.std(axis=1, ddof=1) / np.sqrt(per.shape[1])
    best = int(np.argmin(mean))
    return int(np.flatnonzero(mean <= mean[best] + se[best])[0])


def _converged(part, lam: float, cfg: dict) -> np.ndarray:
    """The float64 reference's beta two Newton steps past its stop."""
    return reference.fit([part], float(lam), tol=cfg["tol"],
                         max_rounds=cfg["max_rounds"], past=2).betas[-1]


def _gap(beta, ref) -> float:
    return float(np.max(np.abs(np.asarray(beta) - ref))
                 / np.max(np.abs(ref)))
