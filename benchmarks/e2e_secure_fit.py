"""End-to-end secure Newton: fused jit-resident iteration vs pre-fusion loop.

Measures the full ``secure_fit`` wall clock (packing included) at the
paper's protocol scale — S institutions, d features, N total records —
for three execution shapes:

* ``loop_reference`` — the pre-fusion baseline: Python loop over
  institutions, one ``local_summaries`` + one protect dispatch per
  institution per iteration, reference (uint64 jnp) protocol backend.
  This is what a pre-fusion caller got from ``secure_fit(parts)`` with
  default arguments (cf. ``benchmarks/runtime.py``).
* ``loop_pallas`` — the same Python loop with the PR-1 fused
  protect/reveal kernels, isolating how much of the win comes from the
  batched/jit-resident iteration itself rather than the protocol kernels.
* ``fused`` — this PR: one batched fused-IRLS summaries launch over all
  institutions, one batched protect, streaming aggregation, reveal and
  Newton update in a single jitted graph; one host sync per iteration.

Every run must converge to the *same* beta: the fused path is checked
against both baselines (tolerance: fixed-point quantization, (S+1)/scale)
and against the pooled ``centralized_fit`` gold standard (paper Fig. 2,
R^2 = 1).  Timing is min-of-repeats after an untimed warmup fit that
triggers all trace/compile work AND the fused path's one-per-study
partition packing (memoized like the jit cache), so the numbers compare
steady-state pipelines, not XLA compilation or data staging.

Interpret-mode caveat: on this CPU container the Pallas protocol kernels
run through the interpreter and the fused-IRLS kernel runs as its XLA
functional simulation (same numerics contract — f32 Gram accumulation,
payload-dtype gradient/deviance; see ``kernels/fused_irls.py``).  On a
TPU the blocked kernels compile (``kernels/backend.py`` decides), and
these CPU timings say nothing about them.  Machine-readable rows land in
BENCH_e2e_secure_fit.json (``--quick`` is the bench_smoke gate size).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    Institution,
    SecureAggregator,
    StudyCoordinator,
    centralized_fit,
    secure_fit,
)
from repro.core.field import fsum
from repro.core.logreg import local_summaries
from repro.data import generate_synthetic


def _pre_pr_secure_fit(parts, lam=1.0, tol=1e-10, max_iter=50,
                       protect="both", aggregator=None, seed=0):
    """Frozen replica of the pre-fusion ``secure_fit`` — the benchmark's
    baseline, kept verbatim-in-behavior so later library changes cannot
    silently speed the comparator: Python loop over institutions, one
    ``local_summaries`` + one protect per institution per iteration,
    eager ``jnp.stack`` share aggregation, per-leaf byte telemetry inside
    the loop, and the Cholesky/cho_solve Newton step.
    """
    agg = aggregator or SecureAggregator()
    key = jax.random.PRNGKey(seed)
    d = parts[0][0].shape[1]
    beta = jnp.zeros((d,), dtype=jnp.float64)
    dev_prev, trace, it, nbytes = np.inf, [], 0, 0
    converged = False
    for it in range(1, max_iter + 1):
        locals_ = [local_summaries(beta, Xj, yj) for Xj, yj in parts]
        protected, plain = [], []
        for s in locals_:
            tree = {}
            if protect in ("gradient", "both"):
                tree["gradient"] = s.gradient
            if protect in ("hessian", "both"):
                tree["hessian"] = s.hessian
            if protect != "none":
                tree["deviance"] = s.deviance
            key, sub = jax.random.split(key)
            protected.append(agg.protect(sub, tree) if tree else {})
            plain.append({k: v for k, v in s._asdict().items()
                          if k not in tree and k != "count"})
            for leaf in jax.tree_util.tree_leaves(protected[-1]):
                nbytes += leaf.size * 8
            for leaf in jax.tree_util.tree_leaves(plain[-1]):
                nbytes += leaf.size * leaf.dtype.itemsize
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs, axis=0), *protected
        )
        summed = jax.tree_util.tree_map(
            lambda s: fsum(s, agg.scheme.field, axis=0, residue_axis=1),
            stacked,
        )
        revealed = agg.reveal(summed) if protect != "none" else {}
        summed_plain = {k: sum(pl[k] for pl in plain) for k in plain[0]} \
            if plain[0] else {}
        gh = revealed.get("hessian", summed_plain.get("hessian"))
        gg = revealed.get("gradient", summed_plain.get("gradient"))
        gdev = revealed.get("deviance", summed_plain.get("deviance"))
        obj = float(gdev) + lam * float(jnp.sum(beta**2))
        trace.append(obj)
        quant_floor = (len(parts) + 1) * 0.5 / agg.codec.scale
        if abs(dev_prev - obj) < max(tol * (1.0 + abs(obj)), quant_floor):
            converged = True
            break
        dev_prev = obj
        A = jnp.asarray(gh, jnp.float64) + lam * jnp.eye(d)
        rhs = jnp.asarray(gg, jnp.float64) - lam * beta
        L = jnp.linalg.cholesky(A)
        beta = beta + jax.scipy.linalg.cho_solve((L, True), rhs)
    return dataclasses.make_dataclass(
        "PrePRFit", ["beta", "iterations", "converged", "bytes_transmitted"]
    )(np.asarray(beta), it, converged, nbytes)


def _ragged_sizes(total: int, s: int) -> list[int]:
    """Mildly uneven split (the paper's random horizontal partitioning is
    near-even at these sizes): +-5% linear ramp around the mean.  The
    fused path pads every institution to N_max, so the ramp width is the
    padding overhead it pays relative to the loop baselines."""
    base = total // s
    sizes = [base + int(base * 0.05 * (2 * j / max(s - 1, 1) - 1))
             for j in range(s)]
    sizes[-1] += total - sum(sizes)
    return sizes


def _make_parts(key, total: int, s: int, d: int):
    study = generate_synthetic(
        key, num_institutions=1, records_per_institution=total, dim=d,
    )
    X, y = study.pooled()
    parts, off = [], 0
    for sz in _ragged_sizes(total, s):
        parts.append((X[off:off + sz], y[off:off + sz]))
        off += sz
    return parts, (X, y)


def _timed_fit(fit_fn, parts, repeats: int, **kw):
    fit_fn(parts, max_iter=2, **kw)  # warmup: trace + compile
    best, res = 1e30, None
    for _ in range(repeats):
        t0 = time.perf_counter()
        res = fit_fn(parts, **kw)
        best = min(best, time.perf_counter() - t0)
    return best, res


def run(num_institutions: int = 8, dim: int = 128, records: int = 200_000,
        protect: str = "both", lam: float = 1.0, repeats: int = 3,
        seed: int = 0):
    parts, (X, y) = _make_parts(
        jax.random.PRNGKey(seed), records, num_institutions, dim
    )
    gold = centralized_fit(X, y, lam=lam)
    ref_agg = SecureAggregator(backend="reference")
    pal_agg = SecureAggregator(backend="pallas")
    quant_tol = (num_institutions + 1) / pal_agg.codec.scale

    runs = {
        # the acceptance baseline: the pre-fusion loop as it shipped
        # (reference-backend protocol, the pre-PR default aggregator)
        "pre_pr_loop": (_pre_pr_secure_fit, dict(aggregator=ref_agg)),
        # the same Python loop on the PR-1 kernels: isolates how much of
        # the win is the fused/batched iteration vs the protocol kernels
        "loop_pallas": (secure_fit,
                        dict(aggregator=pal_agg, fused=False)),
        "fused": (secure_fit, dict(aggregator=pal_agg, fused=True)),
    }
    rows, results = [], {}
    for name, (fit_fn, kw) in runs.items():
        secs, res = _timed_fit(fit_fn, parts, repeats, lam=lam,
                               protect=protect, **kw)
        results[name] = (secs, res)
        err_gold = float(np.abs(res.beta - gold.beta).max())
        r2 = float(np.corrcoef(res.beta, gold.beta)[0, 1] ** 2)
        rows.append({
            "path": name,
            "institutions": num_institutions,
            "dim": dim,
            "records": records,
            "protect": protect,
            "seconds": secs,
            "seconds_per_iter": secs / res.iterations,
            "iterations": res.iterations,
            "converged": res.converged,
            "bytes_transmitted": res.bytes_transmitted,
            "max_abs_err_vs_centralized": err_gold,
            "r2_vs_centralized": r2,
            "pass": res.converged and r2 > 0.999999,
        })

    fused_s, fused_res = results["fused"]
    for base in ("pre_pr_loop", "loop_pallas"):
        base_s, base_res = results[base]
        err = float(np.abs(fused_res.beta - base_res.beta).max())
        row = {
            "check": f"fused speedup vs {base}",
            "protect": protect,
            "baseline_seconds": base_s,
            "fused_seconds": fused_s,
            "speedup": base_s / max(fused_s, 1e-12),
            "max_abs_err_vs_baseline": err,
            "quantization_tol": quant_tol,
            "beta_identical_within_quantization": err <= quant_tol,
        }
        # the headline acceptance gate: >= 3x over the pre-fusion path
        # at identical beta; the loop_pallas row is informational
        if base == "pre_pr_loop":
            row["pass"] = row["speedup"] >= 3.0 and err <= quant_tol
        rows.append(row)
    return rows


def run_coordinator(num_institutions: int = 8, dim: int = 128,
                    records: int = 200_000, protect: str = "both",
                    lam: float = 1.0, repeats: int = 3, seed: int = 0,
                    full_gate: bool = True):
    """Coordinator-driver rows: the deployment-shaped StudyCoordinator on
    the fused cohort round vs its per-institution loop oracle.

    All drivers use the SAME pallas aggregator (the loop already enjoys
    the PR-1 protocol kernels), so the measured win is exactly what the
    cohort-level batched step buys.  Both rungs of the fused round's
    precision ladder are measured:

    * ``coordinator_fused`` — the default f64 ("reference") summaries:
      per-ROUND beta parity with the loop oracle (checked in lockstep,
      every round, against quantization tolerance).  Its speedup is the
      dispatch/protocol fusion win only — the f64 Gram flops are shared
      with the loop, so the ratio compresses toward 1 as N grows.
    * ``coordinator_fused_f32`` — ``summaries_backend="pallas"`` (the
      TPU-dtype Gram, same contract as fused ``secure_fit``): the
      headline round-time win at production N, with CONVERGED-beta
      parity (the mid-run Newton transient amplifies the f32 Hessian
      perturbation past per-round tolerance; the fixed point, set by the
      f64 gradient, is immune).
    """
    parts, (X, y) = _make_parts(
        jax.random.PRNGKey(seed), records, num_institutions, dim
    )
    gold = centralized_fit(X, y, lam=lam)
    agg = SecureAggregator(backend="pallas")
    quant_tol = (num_institutions + 1) / agg.codec.scale

    def make(fused, summaries_backend=None):
        insts = [
            Institution(f"inst{j}", Xj, yj)
            for j, (Xj, yj) in enumerate(parts)
        ]
        return StudyCoordinator(insts, lam=lam, protect=protect,
                                aggregator=agg, seed=seed, fused=fused,
                                summaries_backend=summaries_backend)

    # ---- lockstep per-round parity (also the trace/compile/pack warmup)
    loop, fus = make(False), make(True)
    fus32 = make(True, summaries_backend="pallas")
    max_round_err, max_round_err32 = 0.0, 0.0
    while not (loop.converged or fus.converged) and loop.iteration < 60:
        loop.step()
        fus.step()
        max_round_err = max(max_round_err, float(
            np.abs(np.asarray(loop.beta) - np.asarray(fus.beta)).max()
        ))
        # per-round comparison is only defined while both trajectories
        # are still moving: once either side converges its beta freezes
        # and the difference measures convergence timing, not the Newton
        # transient (same_iterations in the check row catches divergent
        # round counts)
        if not fus32.converged:
            fus32.step()
            if not (loop.converged or fus32.converged):
                max_round_err32 = max(max_round_err32, float(
                    np.abs(np.asarray(loop.beta)
                           - np.asarray(fus32.beta)).max()
                ))
    parity_ok = (loop.converged == fus.converged
                 and loop.iteration == fus.iteration
                 and max_round_err <= quant_tol)

    rows, results = [], {}
    for name, kw in (("coordinator_loop", dict(fused=False)),
                     ("coordinator_fused", dict(fused=True)),
                     ("coordinator_fused_f32",
                      dict(fused=True, summaries_backend="pallas"))):
        best, coord = 1e30, None
        for _ in range(repeats):
            coord = make(**kw)
            t0 = time.perf_counter()
            coord.run()
            best = min(best, time.perf_counter() - t0)
        beta = np.asarray(coord.beta)
        results[name] = (best, coord)
        r2 = float(np.corrcoef(beta, gold.beta)[0, 1] ** 2)
        rows.append({
            "path": name,
            "institutions": num_institutions,
            "dim": dim,
            "records": records,
            "protect": protect,
            "seconds": best,
            "seconds_per_iter": best / coord.iteration,
            "iterations": coord.iteration,
            "converged": bool(coord.converged),
            "bytes_transmitted": int(
                sum(r.bytes_transmitted for r in coord.reports)
            ),
            "max_abs_err_vs_centralized": float(
                np.abs(beta - gold.beta).max()
            ),
            "r2_vs_centralized": r2,
            "pass": bool(coord.converged) and r2 > 0.999999,
        })

    loop_s, loop_c = results["coordinator_loop"]
    round_loop = loop_s / loop_c.iteration
    fus_s, fus_c = results["coordinator_fused"]
    round_fus = fus_s / fus_c.iteration
    rows.append({
        "check": "coordinator fused parity vs loop",
        "protect": protect,
        "seconds_per_round_loop": round_loop,
        "seconds_per_round_fused": round_fus,
        "round_speedup": round_loop / max(round_fus, 1e-12),
        "max_round_beta_err": max_round_err,
        "quantization_tol": quant_tol,
        "per_round_parity_within_quantization": parity_ok,
        # the parity rung's gate: every round within quantization, and
        # the fused round not meaningfully slower than the loop.  At the
        # full config both are bound by the same f64 Gram flops, so the
        # ratio sits at ~1.0 and the quick config (where dispatch
        # dominates and the fusion win is real, ~1.5x) carries the
        # strict not-slower assertion; here we only exclude regressions
        # beyond timer noise.
        "pass": parity_ok and round_loop / max(round_fus, 1e-12) >= (
            0.9 if full_gate else 1.0
        ),
    })
    f32_s, f32_c = results["coordinator_fused_f32"]
    round_f32 = f32_s / f32_c.iteration
    # converged-beta parity measured between the TIMED runs (both driven
    # to their own convergence — the lockstep loop exits when the f64
    # pair converges, which may precede fus32's last round)
    final_err32 = float(
        np.abs(np.asarray(loop_c.beta) - np.asarray(f32_c.beta)).max()
    )
    rows.append({
        "check": "coordinator fused speedup vs loop",
        "protect": protect,
        "baseline_seconds": loop_s,
        "fused_seconds": f32_s,
        "speedup": loop_s / max(f32_s, 1e-12),
        "seconds_per_round_loop": round_loop,
        "seconds_per_round_fused": round_f32,
        "round_speedup": round_loop / max(round_f32, 1e-12),
        "max_round_beta_err": max_round_err32,
        "final_beta_err_vs_loop": final_err32,
        "quantization_tol": quant_tol,
        "final_beta_within_quantization": final_err32 <= quant_tol,
        "same_iterations": loop_c.iteration == f32_c.iteration,
        # the speed rung's gate: >= 2x ROUND time at the full config
        # (>= 1x under --quick) at converged-beta parity over the same
        # number of rounds
        "pass": final_err32 <= quant_tol
        and loop_c.iteration == f32_c.iteration
        and (
            round_loop / max(round_f32, 1e-12) >= (2.0 if full_gate else 1.0)
        ),
    })
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--institutions", type=int, default=8)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--records", type=int, default=200_000,
                    help="total N across all institutions")
    ap.add_argument("--protect", default="both",
                    choices=("none", "gradient", "hessian", "both"))
    ap.add_argument("--lam", type=float, default=1.0)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--quick", action="store_true",
                    help="small config for the bench_smoke gate "
                         "(S=4, d=32, N=20000, 1 repeat; the 3x/2x "
                         "headline gates apply to the full config only)")
    ap.add_argument("--driver", default="both",
                    choices=("both", "secure_fit", "coordinator"),
                    help="which execution driver(s) to benchmark: the "
                         "in-process secure_fit paths, the deployment-"
                         "shaped StudyCoordinator (fused vs loop rounds), "
                         "or both")
    ap.add_argument("--json", default="BENCH_e2e_secure_fit.json",
                    help="machine-readable output path ('' to skip)")
    args = ap.parse_args(argv)

    kw = dict(num_institutions=args.institutions, dim=args.dim,
              records=args.records, protect=args.protect, lam=args.lam,
              repeats=args.repeats)
    if args.quick:
        kw.update(num_institutions=4, dim=32, records=20_000, repeats=1)
    rows = []
    if args.driver in ("both", "secure_fit"):
        rows += run(**kw)
    if args.driver in ("both", "coordinator"):
        rows += run_coordinator(full_gate=not args.quick, **kw)
    rows.append({"config": "quick" if args.quick else "full",
                 "driver": args.driver, **{
        k: kw[k] for k in ("num_institutions", "dim", "records", "protect")
    }})

    out = json.dumps(rows, indent=2)
    print(out)
    if args.json:
        with open(args.json, "w") as f:
            f.write(out + "\n")
    return rows


if __name__ == "__main__":
    main()
