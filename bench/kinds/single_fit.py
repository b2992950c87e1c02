"""Traffic kind ``single_fit``: one analyst, a closed loop of secure fits.

Each job builds a new ``StudyCoordinator`` over the resident sites and
runs it from beta = 0 to its converged, revealed answer (the fused,
scan-resident round: one dispatch and one readback per fit).  The data
stay on the device, so the program's pack cache hits after the warm job,
as it does for a consortium that re-fits its own data.

The jobs cycle through the traffic's lambda grid, each cycle in an order
drawn from the seed: every seed gives the same work, in another order.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from .. import data, reference, work

__all__ = ["Answer", "Cell"]


@dataclasses.dataclass
class Answer:
    """What one fit returned to the analyst."""

    lam: float
    beta: np.ndarray
    objective: float  # the revealed objective at ``beta``
    rounds: int  # secure rounds executed, the stopping one included
    converged: bool
    first_step: float  # ||beta_1 - beta_0|| of the first round

    @property
    def steps(self) -> int:
        """Newton updates applied: the stopping round applies none."""
        return self.rounds - 1 if self.converged else self.rounds


def _program():
    """The system under test, by its public entry points."""
    from repro import core

    return core


class Cell:
    """``config`` and ``traffic`` as loaded from their files."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        self.rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, 1]))
        self.parts = None
        self.sites = None
        self.agg = None

    # -- set-up ---------------------------------------------------------------
    def setup(self) -> dict:
        """Data, sites and the warm job; returns the seconds of each."""
        t0 = time.perf_counter()
        core = _program()
        cfg = self.config
        self.parts = data.make_parts(cfg, data.seed_key(self.seed, 0))
        jax.block_until_ready(self.parts)
        t1 = time.perf_counter()
        self.sites = [core.Institution(f"site{j}", X, y)
                      for j, (X, y) in enumerate(self.parts)]
        shamir = cfg["shamir"]
        self.agg = core.SecureCollective(
            scheme=core.ShamirScheme(threshold=shamir["threshold"],
                                     num_shares=shamir["centers"]),
            codec=core.FixedPointCodec(frac_bits=shamir["frac_bits"]),
            backend="pallas",
        )
        if len(self.agg.scheme.field.moduli) != shamir["residues"]:
            raise ValueError("the program's field does not have the "
                             "configuration's residues")
        self.warm()
        t2 = time.perf_counter()
        return {"data_s": t1 - t0, "warm_job_s": t2 - t1}

    def warm(self):
        """The warm job: the pack and the one round program."""
        self.run_job(float(self.traffic["lambdas"][0]))

    def jobs(self):
        """The endless job stream: lambda values, cycle by cycle."""
        grid = [float(v) for v in self.traffic["lambdas"]]
        while True:
            for i in self.rng.permutation(len(grid)):
                yield grid[i]

    def run_job(self, lam: float) -> Answer:
        core = _program()
        cfg = self.config
        coord = core.StudyCoordinator(
            self.sites, lam=lam, protect=cfg["protect"], aggregator=self.agg,
            tol=cfg["tol"], seed=int(self.rng.integers(2**31)), fused=True,
            rounds="scan", summaries_backend=cfg["summaries"],
        )
        beta = coord.run(max_iter=cfg["max_rounds"])
        return Answer(lam, np.asarray(beta, np.float64),
                      float(coord.trace[-1]), int(coord.iteration),
                      bool(coord.converged),
                      float(coord.reports[0].step_norm))

    def release(self):
        """Free what the program holds on the device (its packs)."""
        _program().pack_cache_clear()
        self.sites = None

    # -- accounting -----------------------------------------------------------
    def job_flops(self, answer: Answer) -> float:
        cfg = self.config
        return answer.rounds * work.fit_round_flops(cfg["rows"],
                                                    cfg["features"])

    def kernel_work(self, answers) -> dict:
        """The least work of the named kernels over ``answers``: one
        summaries launch and one protect and one reveal launch a round.
        ``irls``: (operations, bytes); ``shamir``: bytes."""
        cfg, sh = self.config, self.config["shamir"]
        r = sum(a.rounds for a in answers)
        flops, nbytes = work.irls_kernel(cfg["rows"], cfg["sites"],
                                         cfg["features"])
        shamir = (work.share_kernel(cfg["sites"], cfg["features"],
                                    cfg["protect"], sh["residues"],
                                    sh["threshold"], sh["centers"])
                  + work.reconstruct_kernel(cfg["features"], cfg["protect"],
                                            sh["residues"], sh["threshold"]))
        return {"irls": (r * flops, r * nbytes), "shamir": r * shamir}

    # -- the comparison with the plain reference ------------------------------
    def checked_lambdas(self, answers) -> list[float]:
        """The lambdas whose every job is compared: drawn from the seed."""
        seen = sorted({a.lam for a in answers})
        k = min(int(self.traffic["check_lambdas"]), len(seen))
        pick = np.random.default_rng(
            np.random.SeedSequence([self.seed, 2])).choice(
                len(seen), size=k, replace=False)
        return [seen[i] for i in sorted(pick)]

    def compare(self, answers, parts_ref, lams=None) -> dict:
        """The worst of each compared number over the checked jobs.

        The reference walks Newton's method from zero in float64 on
        ``parts_ref`` (the same rows), with its own stopping rule:

        * ``beta_gap``: the answer against the reference's iterate after
          as many Newton steps, max-norm over the reference's max-norm
          (the protect/reveal chain, the summaries and the solve);
        * ``rounds_gap``: the answer's rounds against the reference's own;
        * ``objective_gap``: the revealed objective against the
          reference's objective at the answer's beta, relative (the
          deviance through summaries and reveal);
        * ``first_step_gap``: the first round's step norm against the
          reference's, relative (the first Hessian, so the Gram).

        A fit that does not converge runs all its rounds: ``rounds_gap``
        shows it.
        """
        cfg = self.config
        parts_ref = [reference.pool(parts_ref)]
        lams = self.checked_lambdas(answers) if lams is None else lams
        checked = [a for a in answers if a.lam in lams]
        steps = {lam: max([1] + [a.steps for a in checked if a.lam == lam])
                 for lam in lams}
        refs = {lam: reference.fit(parts_ref, lam, tol=cfg["tol"],
                                   max_rounds=cfg["max_rounds"],
                                   min_steps=steps[lam])
                for lam in lams}
        objs = {}
        out = {"beta_gap": 0.0, "rounds_gap": 0.0, "objective_gap": 0.0,
               "first_step_gap": 0.0}
        for a in checked:
            ref = refs[a.lam]
            target = ref.betas[min(a.steps, len(ref.betas) - 1)]
            out["beta_gap"] = max(out["beta_gap"], float(
                np.max(np.abs(a.beta - target)) / np.max(np.abs(target))))
            out["rounds_gap"] = max(out["rounds_gap"],
                                    float(abs(a.rounds - ref.rounds)))
            key = (a.lam, a.beta.tobytes())
            if key not in objs:
                objs[key] = reference.objective(a.beta, parts_ref, a.lam)
            out["objective_gap"] = max(out["objective_gap"], float(
                abs(a.objective - objs[key]) / abs(objs[key])))
            step1 = float(np.linalg.norm(ref.betas[1] - ref.betas[0]))
            out["first_step_gap"] = max(out["first_step_gap"], float(
                abs(a.first_step - step1) / step1))
        out["jobs_compared"] = float(len(checked))
        return out

    def control_answers(self, lams, parts_dev, prec) -> list[Answer]:
        """The reference in the program's place, at precision ``prec``."""
        cfg = self.config
        parts_dev = [reference.pool(parts_dev)]
        out = []
        for lam in lams:
            t = reference.fit(parts_dev, lam, prec, tol=cfg["tol"],
                              max_rounds=cfg["max_rounds"])
            out.append(Answer(
                lam, t.beta, t.objectives[t.rounds - 1], t.rounds,
                t.converged,
                float(np.linalg.norm(t.betas[1] - t.betas[0]))
                if len(t.betas) > 1 else 0.0))
        return out
