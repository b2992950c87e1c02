"""End-to-end training driver.

Two pipelines behind one CLI, selected by ``--arch``:

* ``--arch logreg_paper`` — the paper's pipeline: S institutions run
  Algorithm 1 (distributed summaries -> Shamir shares -> secure aggregation
  at the Computation Centers -> Newton step) with straggler/center-failure
  tolerance and checkpoint/restart of protocol state.

* ``--arch <lm-arch>`` — LM training on the unified decoder stack, with the
  paper's technique as a first-class optimizer feature: ``--secure-agg
  shamir`` replaces the cross-institution gradient reduction with
  secret-shared aggregation (core.secure_agg), exactly the role H_j/g_j
  sharing plays in Algorithm 1.  ``--institutions S`` splits every global
  batch S ways; per-institution grads are protected before any aggregation.
  Supports AdamW, grad clipping, checkpoint/restart (atomic, retain-k,
  async), deterministic failure injection and elastic re-meshing plans.

Examples (CPU-runnable):
  PYTHONPATH=src python -m repro.launch.train --arch logreg_paper \
      --study synthetic --protect gradient
  PYTHONPATH=src python -m repro.launch.train --arch deepseek_7b --smoke \
      --steps 20 --secure-agg shamir --institutions 4
  PYTHONPATH=src python -m repro.launch.train --arch qwen2_5_32b --smoke \
      --steps 50 --checkpoint-dir /tmp/ck --resume
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    # --- logreg pipeline
    ap.add_argument("--study", default="synthetic",
                    help="insurance | parkinsons.motor | parkinsons.total | "
                         "synthetic")
    ap.add_argument("--protect", default="gradient",
                    choices=["none", "gradient", "hessian", "both"])
    ap.add_argument("--lam", type=float, default=1.0)
    ap.add_argument("--l1", type=float, default=0.0,
                    help="L1 penalty (elastic net); institution protocol "
                         "unchanged, center solver switches to prox-Newton")
    ap.add_argument("--tol", type=float, default=1e-10)
    ap.add_argument("--scale", type=float, default=1.0,
                    help="row-count scale for quick runs")
    ap.add_argument("--centers", type=int, default=3)
    ap.add_argument("--threshold", type=int, default=2)
    ap.add_argument("--rounds", default="step", choices=["step", "scan"],
                    help="round execution for the secure fit: 'step' "
                         "re-enters Python every Newton round; 'scan' runs "
                         "whole blocks of rounds as ONE lax.scan — one host "
                         "sync per block (requires --fused)")
    ap.add_argument("--rounds-per-sync", type=int, default=None,
                    metavar="K",
                    help="scan block size: K rounds per host sync (default "
                         "None = the whole fit as one block; smaller blocks "
                         "let the fault supervisor and checkpoints cut in)")
    ap.add_argument("--fused", action="store_true",
                    help="cohort-level batched coordinator rounds (pallas "
                         "backend); per-round parity with the loop oracle "
                         "within fixed-point quantization")
    ap.add_argument("--select-lambda", default=None, metavar="GRID",
                    help="choose λ by secure K-fold cross-validation over "
                         "a comma-separated descending grid (e.g. "
                         "'30,10,3,1,0.3') instead of fitting --lam; runs "
                         "the batched scanned sweep (pallas backend), "
                         "prints the CV curve, picks the 1-SE λ, and "
                         "refits on all data")
    ap.add_argument("--folds", type=int, default=5,
                    help="CV folds for --select-lambda")
    ap.add_argument("--deadline", type=float, default=None,
                    help="straggler deadline (simulated seconds)")
    # --- LM pipeline
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--secure-agg", default="none",
                    choices=["none", "shamir"])
    ap.add_argument("--secure-backend", default="pallas",
                    choices=["pallas", "reference"],
                    help="shamir aggregation wire: 'pallas' runs the whole "
                         "cohort round on the flat-buffer uint32 wire (one "
                         "batched encode+share launch, one exact uint64 "
                         "reduction, t-subset reveal); 'reference' keeps "
                         "the per-leaf uint64 oracle loop")
    ap.add_argument("--institutions", type=int, default=4,
                    help="batch splits treated as paper institutions")
    ap.add_argument("--compress", action="store_true",
                    help="int8 error-feedback gradient compression "
                         "(plain mode only)")
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject an institution failure at this step")
    # --- common
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--out", default=None, help="write metrics JSON here")
    return ap.parse_args(argv)


# --------------------------------------------------------------- logreg path
def run_logreg(args) -> dict:
    import jax
    import jax.numpy as jnp

    from ..checkpoint import CheckpointManager
    from ..core.newton import centralized_fit
    from ..core.protocol import Institution, StudyCoordinator
    from ..core.secure_agg import SecureAggregator
    from ..core.shamir import ShamirScheme
    from ..data.datasets import load_study

    study = load_study(args.study, seed=args.seed, scale=args.scale)
    if args.select_lambda:
        from ..selection import SelectionCoordinator

        lambdas = [float(x) for x in args.select_lambda.split(",")]
        agg = SecureAggregator(
            scheme=ShamirScheme(threshold=args.threshold,
                                num_shares=args.centers,
                                backend="pallas"),
            overflow_check=True,
        )
        insts = [
            Institution(f"inst{j}", Xj, yj)
            for j, (Xj, yj) in enumerate(study.parts)
        ]
        coord = SelectionCoordinator(
            insts, lambdas, num_folds=args.folds, l1=args.l1,
            protect=args.protect, aggregator=agg, deadline=args.deadline,
            tol=args.tol, seed=args.seed,
        )
        report = coord.run_path()
        print("\n".join(report.summary_lines()))
        out = {
            "pipeline": "logreg_paper", "study": study.name,
            "mode": "select-lambda",
            "lambdas": list(report.lambdas),
            "folds": args.folds,
            "cv_mean_deviance": [float(v) for v in report.cv_mean],
            "cv_se": [float(v) for v in report.cv_se],
            "cv_accuracy": [float(v) for v in report.cv_accuracy],
            "lambda_best": report.lambda_best,
            "lambda_1se": report.lambda_1se,
            "secure_rounds": report.rounds_total,
            "bytes_per_round": report.bytes_per_round,
            "bytes_transmitted": report.bytes_total,
            "nonzero_coefs": int((np.abs(report.beta) > 1e-6).sum()),
            "features": study.num_features,
            "protect": args.protect,
        }
        print(json.dumps(out, indent=2))
        return out
    if args.l1 > 0.0:
        from ..core.newton import secure_fit

        res = secure_fit(study.parts, lam=args.lam, l1=args.l1,
                         tol=args.tol, protect=args.protect)
        out = {
            "pipeline": "logreg_paper", "study": study.name,
            "regularization": f"elastic-net lam={args.lam} l1={args.l1}",
            "iterations": res.iterations, "converged": res.converged,
            "nonzero_coefs": int((abs(res.beta) > 1e-6).sum()),
            "features": study.num_features,
            "total_seconds": res.total_seconds,
        }
        print(json.dumps(out, indent=2))
        return out
    # overflow_check: armed by default on every launch secure path — the
    # fixed-point headroom assert is a fixed ~1-3 ms/round host callback
    # (<= 2% of a production fused round; benchmarks/fault_overhead.py),
    # and a raise beats silently saturating into a plausible reveal
    agg = SecureAggregator(
        scheme=ShamirScheme(threshold=args.threshold,
                            num_shares=args.centers,
                            backend="pallas" if args.fused else "reference"),
        overflow_check=True,
    )
    insts = [
        Institution(f"inst{j}", Xj, yj)
        for j, (Xj, yj) in enumerate(study.parts)
    ]
    coord = StudyCoordinator(
        insts, lam=args.lam, protect=args.protect, aggregator=agg,
        deadline=args.deadline, tol=args.tol, seed=args.seed,
        fused=args.fused, rounds=args.rounds,
        rounds_per_sync=args.rounds_per_sync,
    )

    ckpt = None
    if args.checkpoint_dir:
        ckpt = CheckpointManager(args.checkpoint_dir, retain=3)
        if args.resume and ckpt.latest_step() is not None:
            state, step = ckpt.restore(
                {"beta": np.asarray(coord.beta), "obj_prev": np.float64(0)}
            )
            coord.beta = jnp.asarray(state["beta"])
            coord._obj_prev = float(state["obj_prev"])
            coord.iteration = step
            print(f"resumed protocol at iteration {step}")

    t0 = time.perf_counter()
    while not coord.converged and coord.iteration < 50:
        rep = coord.step()
        print(f"iter {rep.iteration:2d} obj={rep.objective:.10f} "
              f"responders={len(rep.responders)} "
              f"stragglers={rep.stragglers}")
        if ckpt and rep.iteration % 1 == 0:
            ckpt.save(rep.iteration, {
                "beta": np.asarray(coord.beta),
                "obj_prev": np.float64(coord._obj_prev),
            })
    total_s = time.perf_counter() - t0

    gold = centralized_fit(*study.pooled(), lam=args.lam, tol=args.tol)
    r2 = float(np.corrcoef(np.asarray(coord.beta), gold.beta)[0, 1] ** 2)
    out = {
        "pipeline": "logreg_paper",
        "study": study.name,
        "samples": study.num_samples,
        "features": study.num_features,
        "iterations": coord.iteration,
        "converged": bool(coord.converged),
        "r2_vs_gold": r2,
        "max_abs_err_vs_gold": float(
            np.max(np.abs(np.asarray(coord.beta) - gold.beta))
        ),
        "total_seconds": total_s,
        "bytes_transmitted": int(
            sum(r.bytes_transmitted for r in coord.reports)
        ),
        "protect": args.protect,
    }
    print(json.dumps(out, indent=2))
    return out


# ------------------------------------------------------------------- LM path
def run_lm(args) -> dict:
    import jax
    import jax.numpy as jnp

    from ..checkpoint import CheckpointManager
    from ..configs import get_config, smoke_config
    from ..core.secure_agg import SecureAggregator
    from ..distributed import MeshRules
    from ..models import transformer as T
    from ..optim.adamw import AdamWConfig, adamw_init, adamw_update
    from ..optim.compression import compressed_psum, init_error_feedback
    from ..runtime import FailureInjector, HeartbeatMonitor, SimClock

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    rules = MeshRules(mesh=None)  # single-host run; dry-run covers the pod mesh
    key = jax.random.PRNGKey(args.seed)
    key, kp = jax.random.split(key)
    params = T.init_params(kp, cfg)
    # warmup must fit the run: the config default (100 steps) left short
    # smoke runs training at ~1% of the requested lr, so their loss
    # trajectory was pure batch noise
    opt_cfg = AdamWConfig(
        lr=args.lr, warmup_steps=min(100, max(1, args.steps // 2))
    )
    opt_state = adamw_init(params)
    S = max(1, args.institutions)
    agg = SecureAggregator(backend=args.secure_backend,
                           overflow_check=True) \
        if args.secure_agg == "shamir" else None
    err_fb = init_error_feedback(params) if args.compress else None

    B, L = args.batch, args.seq_len
    if B % S:
        raise SystemExit(f"--batch {B} must be divisible by "
                         f"--institutions {S}")

    # The synthetic stream is a small FIXED corpus the loop cycles over
    # (epochs), not a fresh i.i.d. draw per step.  Fresh uniform tokens
    # every step have no learnable structure beyond the marginal, so a
    # short run's first-vs-last loss compared uncorrelated batch noise
    # and the convergence smoke failed stochastically; on a fixed corpus
    # the loss decreases deterministically (and identically under secure
    # aggregation — fixed-point quantization is ~1e-9 per grad element).
    corpus_batches = 4

    def data_batch(step: int, live: np.ndarray):
        """Deterministic synthetic LM batch, per-institution slices."""
        k = jax.random.fold_in(
            jax.random.PRNGKey(args.seed + 1), step % corpus_batches
        )
        tokens = jax.random.randint(k, (B, L + 1), 0, cfg.vocab_size)
        batch = {"labels": tokens[:, 1:].astype(jnp.int32)}
        if cfg.frontend == "embeddings":
            ke = jax.random.fold_in(k, 7)
            batch["embeds"] = jax.random.normal(
                ke, (B, L, cfg.d_model), dtype=jnp.bfloat16
            )
        else:
            batch["tokens"] = tokens[:, :-1].astype(jnp.int32)
        return batch

    def inst_slices(batch):
        return [
            jax.tree_util.tree_map(lambda x: x[j * (B // S):(j + 1) * (B // S)],
                                   batch)
            for j in range(S)
        ]

    grad_fn = jax.jit(
        lambda p, b: jax.value_and_grad(T.loss_fn, has_aux=True)(
            p, b, cfg, rules
        )
    )

    @jax.jit
    def apply_update(grads, opt_state, params):
        return adamw_update(grads, opt_state, params, opt_cfg)

    # --- fault-tolerance wiring
    clock = SimClock()
    monitor = HeartbeatMonitor(clock, timeout=5.0)
    for j in range(S):
        monitor.register(f"inst{j}")
    injector = FailureInjector(
        {args.fail_at: [f"inst{S - 1}"]} if args.fail_at is not None else {}
    )

    ckpt = None
    start = 0
    if args.checkpoint_dir:
        ckpt = CheckpointManager(args.checkpoint_dir, retain=3,
                                 async_writes=False)
        if args.resume and ckpt.latest_step() is not None:
            state, start = ckpt.restore(
                {"params": params, "opt": opt_state}
            )
            params, opt_state = state["params"], state["opt"]
            print(f"resumed LM training at step {start}")

    losses = []
    t0 = time.perf_counter()
    for step in range(start, args.steps):
        clock.advance(1.0)
        killed = injector.apply(step, monitor)
        if killed:
            print(f"step {step}: institutions failed: {killed}")
        live = [w for w in monitor.alive()]
        live_idx = sorted(int(w[4:]) for w in live)
        if not live_idx:
            raise RuntimeError("no live institutions")
        for w in live:
            monitor.beat(w)

        batch = data_batch(step, live_idx)
        slices = inst_slices(batch)
        # per-institution local computation (paper's distributed phase)
        per_inst = []
        loss_acc = 0.0
        for j in live_idx:
            (loss, metrics), grads = grad_fn(params, slices[j])
            per_inst.append(grads)
            loss_acc += float(loss)
        loss = loss_acc / len(live_idx)

        # cross-institution aggregation (paper's centralized phase)
        if agg is not None:
            key, kk = jax.random.split(key)
            if agg.backend == "pallas":
                # flat-buffer wire: the live cohort's grad trees stack
                # S-leading and the whole round is one batched
                # encode+share launch -> exact uint64 reduction over the
                # institution axis -> one t-subset reveal (the same round
                # helper the fused protocol drivers run); per-institution
                # gradients only ever exist as shares past this point
                stacked = jax.tree_util.tree_map(
                    lambda *gs: jnp.stack(gs, axis=0), *per_inst
                )
                summed = agg.secure_round_batched(
                    kk, stacked, dtype=jnp.float32
                )
            else:
                # per-leaf uint64 oracle loop (debug/audit rung)
                protected = [
                    agg.protect(jax.random.fold_in(kk, j), g)
                    for j, g in zip(live_idx, per_inst)
                ]
                summed = agg.reveal(agg.aggregate(protected),
                                    dtype=jnp.float32)
            grads = jax.tree_util.tree_map(
                lambda x: (x / len(live_idx)).astype(jnp.float32), summed
            )
        else:
            grads = jax.tree_util.tree_map(
                lambda *gs: sum(g.astype(jnp.float32) for g in gs)
                / len(live_idx),
                *per_inst,
            )

        params, opt_state, om = apply_update(grads, opt_state, params)
        losses.append(loss)
        if step % args.log_every == 0:
            print(f"step {step:4d} loss={loss:.4f} "
                  f"gnorm={float(om['grad_norm']):.3f} "
                  f"live={len(live_idx)}/{S}")
        if ckpt and (step + 1) % args.checkpoint_every == 0:
            ckpt.save(step + 1, {"params": params, "opt": opt_state})

    total_s = time.perf_counter() - t0
    if ckpt:
        ckpt.save(args.steps, {"params": params, "opt": opt_state})
        ckpt.close()
    out = {
        "pipeline": "lm",
        "arch": cfg.name,
        "params": T.count_params(cfg),
        "steps": args.steps - start,
        "secure_agg": args.secure_agg,
        "secure_backend": args.secure_backend
        if args.secure_agg != "none" else None,
        "institutions": S,
        "loss_first": losses[0] if losses else None,
        "loss_last": losses[-1] if losses else None,
        "seconds": total_s,
    }
    print(json.dumps(out, indent=2))
    return out


def main(argv=None):
    from .compile_cache import enable_compile_cache

    args = parse_args(argv)
    enable_compile_cache()
    if args.arch == "logreg_paper":
        out = run_logreg(args)
    else:
        out = run_lm(args)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)
    return out


if __name__ == "__main__":
    main()
