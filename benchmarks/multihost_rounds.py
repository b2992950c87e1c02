"""Multi-host secure rounds: scan residency + CPU-mesh round latency vs S.

Two measurements, one JSON:

**Part (a) — whole-fit scan residency.**  The per-round fused
``SecureFitDriver`` re-enters Python every round (one jit dispatch + one
host readback of the objective per round); ``rounds="scan"`` runs the
entire fit as ONE ``lax.scan`` with in-graph rng and reads the deviance
trace back once.  Measured at the e2e acceptance config (S=8, d=128,
N=2e5; ``--quick`` shrinks N): wall clock per path, host syncs per fit
(the scan's structural claim: 1 vs one-per-round), and beta parity vs
the per-round loop oracle (exactly 0 — revealed aggregates are
rng-independent, see ``core/scanfit.py``).  On this repo's single-core
CI host the fit is compute-bound (~190 ms/round of f32 Gram at the full
config vs ~1 ms/round of dispatch), so the wall-clock ratio sits near
1x; the JSON therefore also records *modeled* speedups at nominal
per-sync round-trip latencies (10/50/100 ms — the regime a multi-host
deployment actually occupies, where each per-round host sync crosses
the supervisor's network), computed from the measured compute time and
round count with zero extrapolation of the compute itself.  The CI gate
rides on the invariants: one host sync, beta parity, no wall-clock
regression; the 1.5x accelerator target is reported against both the
measured and the modeled ratios.

**Part (b) — CPU-mesh round latency vs S.**  One subprocess per S (the
forced host device count must be owned before jax initializes —
``distributed/xla_flags.mesh_env`` builds the child env; the GPU-only
latency-hiding flags stay off, CPU builds abort on unknown
``--xla_gpu_*`` flags) runs ``scan_secure_rounds`` over a 1D pod mesh at
S ∈ {8, 64, 256} and reports steady-state seconds/round for both wire
paths (replicated + sharded reveal) plus the static bytes/round/device
model.  Gate: round latency at S=256 ≤ 1.5x S=8 — secure-round cost
must be flat in the institution count, not linear.  A 2D
(pod x share) child validates the distributed Lagrange reveal
(``secure_psum_2d``) end to end and times its round.

``--real-kernels`` additionally emits the ``interpret=False`` block-size
knob validation rows (``kernels/tuning.py``): pure arithmetic VMEM
checks of the compiled-path blocking.  On the CPU CI mesh the kernels
still run interpreted — the flag changes nothing about execution there
(documented no-op), it only proves the knobs would compile.

Writes BENCH_multihost_rounds.json (smoke name under --quick).
"""
from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODELED_RTTS_MS = (10.0, 50.0, 100.0)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--records", type=int, default=200_000,
                    help="total N for part (a) (acceptance: 2e5)")
    ap.add_argument("--institutions", type=int, default=8)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--devices-list", type=int, nargs="+",
                    default=[8, 64, 256],
                    help="pod-mesh sizes for part (b), one subprocess each")
    ap.add_argument("--rounds", type=int, default=2,
                    help="scanned rounds per part-(b) timing")
    ap.add_argument("--params", type=int, default=128,
                    help="per-round tree elements for part (b)")
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--real-kernels", action="store_true",
                    help="emit interpret=False block-size knob validation "
                         "rows (no-op for execution on CPU CI)")
    ap.add_argument("--quick", action="store_true",
                    help="smoke scale: N=8000, S list {8, 64}, smoke JSON")
    ap.add_argument("--json", default=None)
    # internal: subprocess entrypoints (one forced device count each)
    ap.add_argument("--child", choices=["1d", "2d"], default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--child-devices", type=int, default=0,
                    help=argparse.SUPPRESS)
    return ap.parse_args(argv)


# ---------------------------------------------------------------- part (a)

def _timed_driver(parts, lam, agg, repeats, **kw):
    import jax
    from repro.core.newton import SecureFitDriver

    def fit():
        drv = SecureFitDriver(parts, lam=lam, protect="both",
                              aggregator=agg, fused=True, **kw)
        drv.run()
        jax.block_until_ready(drv.beta)
        return drv

    drv = fit()  # warmup: trace + compile off the clock
    best = 1e30
    for _ in range(repeats):
        t0 = time.perf_counter()
        drv = fit()
        best = min(best, time.perf_counter() - t0)
    return best, drv


def run_fit_comparison(records, institutions, dim, repeats):
    import jax
    import numpy as np

    from repro.core import SecureAggregator
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    from e2e_secure_fit import _make_parts

    parts, _ = _make_parts(
        jax.random.PRNGKey(0), records, institutions, dim
    )
    agg = SecureAggregator(backend="pallas")
    quant_tol = (institutions + 1) / agg.codec.scale

    t_step, d_step = _timed_driver(parts, 1.0, agg, repeats)
    t_scan, d_scan = _timed_driver(parts, 1.0, agg, repeats,
                                   rounds="scan")
    err = float(np.max(np.abs(
        np.asarray(d_step.beta) - np.asarray(d_scan.beta)
    )))
    rounds = d_step.iteration
    speedup = t_step / max(t_scan, 1e-12)
    rows = [
        {"path": "fit_per_round", "records": records,
         "institutions": institutions, "dim": dim, "seconds": t_step,
         "rounds": rounds, "host_syncs": rounds,
         "converged": bool(d_step.converged)},
        {"path": "fit_scan", "records": records,
         "institutions": institutions, "dim": dim, "seconds": t_scan,
         "rounds": d_scan.iteration, "host_syncs": 1,
         "converged": bool(d_scan.converged)},
    ]
    # modeled multi-host ratio: every host sync costs one supervisor
    # round trip; compute time is the MEASURED scan time (no projection)
    modeled = {}
    for rtt_ms in MODELED_RTTS_MS:
        rtt = rtt_ms / 1e3
        modeled[f"modeled_speedup_at_{rtt_ms:.0f}ms_rtt"] = (
            (t_step + rounds * rtt) / (t_scan + rtt)
        )
    rows.append({
        "check": "scan residency vs per-round fused",
        "speedup": speedup,
        "host_syncs_per_round_path": rounds,
        "host_syncs_scan_path": 1,
        "max_abs_err_vs_loop_oracle": err,
        "quantization_tol": quant_tol,
        "target_accelerator_speedup": 1.5,
        "meets_target_measured": speedup >= 1.5,
        **modeled,
        # the CI gate: structural invariants that hold on any backend —
        # one sync per fit, oracle parity, and no wall-clock regression
        # (the 1.5x target is dispatch-bound; this host is compute-bound
        # on one core, see module docstring)
        "pass": (err <= quant_tol
                 and d_scan.iteration == rounds
                 and speedup >= 0.9),
    })
    return rows


# ---------------------------------------------------------------- part (b)

def _round_payload(params: int, devices: int, agg) -> dict:
    """Static per-device wire bytes for ONE secure round (ring model)."""
    from repro.core.flatbuf import LANES, ROW_ALIGN, _rows_for

    t = agg.scheme.threshold
    num_r = agg.scheme.field.num_residues
    ring = (devices - 1) / devices if devices > 1 else 1.0
    rows = _rows_for(params, ROW_ALIGN)
    rows_sh = _rows_for(params, math.lcm(ROW_ALIGN, devices))
    buf = num_r * rows * LANES * 4          # uint32 share wire
    buf_sh = num_r * rows_sh * LANES * 4
    return {
        "replicated": int(2 * t * buf * ring),
        "sharded": int((t * buf_sh + rows_sh * LANES * 4) * ring),
    }


def run_child_1d(devices: int, params: int, rounds: int, repeats: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.secure_agg import SecureAggregator
    from repro.distributed.multihost import run_scanned_rounds

    agg = SecureAggregator(backend="pallas")
    tree = {"g": 0.01 * jax.random.normal(jax.random.PRNGKey(1), (params,),
                                          jnp.float32)}
    out = {"devices": devices, "params": params, "rounds": rounds}
    for reveal in ("replicated", "sharded"):
        def go():
            final, trace = run_scanned_rounds(
                devices, tree, jax.random.PRNGKey(7), rounds,
                aggregator=agg, reveal=reveal,
            )
            jax.block_until_ready(trace)
            return final

        final = go()  # warmup
        best = 1e30
        for _ in range(repeats):
            t0 = time.perf_counter()
            final = go()
            best = min(best, time.perf_counter() - t0)
        # the mean-preserving chain: every round reveals sum then divides
        # by D, so the final tree must equal the input within quantization
        err = float(np.max(np.abs(
            np.asarray(final["g"]) - np.asarray(tree["g"])
        )))
        out[f"seconds_per_round_{reveal}"] = best / rounds
        out[f"max_abs_err_{reveal}"] = err
        out[f"ok_{reveal}"] = err <= rounds * (devices + 1) / agg.codec.scale
    out["bytes_per_round_per_device"] = _round_payload(params, devices, agg)
    return out


def run_child_2d(pods: int, params: int, repeats: int):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.core.secure_agg import SecureAggregator, secure_psum
    from repro.distributed.multihost import pod_mesh, pod_share_mesh, \
        secure_psum_2d
    from repro.distributed.sharding import POD_AXIS

    agg = SecureAggregator(backend="pallas")
    t = agg.scheme.threshold
    tree = {"g": 0.01 * jax.random.normal(jax.random.PRNGKey(1), (params,),
                                          jnp.float32)}
    key = jax.random.PRNGKey(7)
    mesh2 = pod_share_mesh(pods, t)
    fn2 = jax.jit(jax.shard_map(
        lambda: secure_psum_2d(tree, key, aggregator=agg),
        mesh=mesh2, in_specs=(), out_specs=P(), check_vma=False,
    ))
    out2 = fn2()
    jax.block_until_ready(out2)
    best = 1e30
    for _ in range(repeats):
        t0 = time.perf_counter()
        out2 = fn2()
        jax.block_until_ready(out2)
        best = min(best, time.perf_counter() - t0)
    # oracle: the 1D wire on a pods-sized mesh reveals the same field
    # encoding, so the decoded floats must agree BITWISE
    mesh1 = pod_mesh(pods)
    out1 = jax.jit(jax.shard_map(
        lambda: secure_psum(tree, POD_AXIS, key, aggregator=agg),
        mesh=mesh1, in_specs=(), out_specs=P(), check_vma=False,
    ))()
    err = float(np.max(np.abs(
        np.asarray(out2["g"], np.float64) - np.asarray(out1["g"],
                                                       np.float64)
    )))
    return {"pods": pods, "share_devices": t, "params": params,
            "seconds_per_round": best, "max_abs_err_vs_1d_wire": err,
            "ok": err == 0.0}


def _spawn_child(mode: str, devices: int, pods: int, args) -> dict:
    """Run one forced-device-count measurement in a fresh process."""
    from repro.distributed.xla_flags import mesh_env

    # latency_hiding stays False: the --xla_gpu_* overlap flags abort
    # CPU-only XLA builds (unknown-flag check); GPU launches opt in
    env = mesh_env(host_device_count=devices, base=os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    cmd = [sys.executable, os.path.abspath(__file__),
           "--child", mode, "--child-devices", str(pods),
           "--params", str(args.params), "--rounds", str(args.rounds),
           "--repeats", str(args.repeats)]
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=560)
    if r.returncode != 0:
        raise RuntimeError(
            f"child {mode} S={devices} failed:\n{r.stderr[-2000:]}"
        )
    for line in r.stdout.splitlines():
        if line.startswith("CHILD_JSON: "):
            return json.loads(line[len("CHILD_JSON: "):])
    raise RuntimeError(f"child {mode} S={devices} emitted no JSON row")


def run_mesh_sweep(args) -> list:
    rows = []
    latencies = {}
    for s in args.devices_list:
        row = _spawn_child("1d", s, s, args)
        latencies[s] = row["seconds_per_round_replicated"]
        rows.append({"mesh": "pod_1d", **row})
    s_lo, s_hi = min(args.devices_list), max(args.devices_list)
    ratio = latencies[s_hi] / max(latencies[s_lo], 1e-12)
    rows.append({
        "check": "round latency flat in institutions",
        "s_low": s_lo, "s_high": s_hi,
        "seconds_per_round_low": latencies[s_lo],
        "seconds_per_round_high": latencies[s_hi],
        "latency_ratio": ratio,
        "gate": 1.5,
        "pass": ratio <= 1.5 and all(
            r.get("ok_replicated") and r.get("ok_sharded")
            for r in rows if "ok_replicated" in r
        ),
    })
    # 2D (pod x share) distributed-reveal datapoint at the smallest S:
    # pods * threshold forced devices
    from repro.core.secure_agg import SecureAggregator

    pods_2d = s_lo
    scheme_t = SecureAggregator().scheme.threshold
    row2 = _spawn_child("2d", pods_2d * scheme_t, pods_2d, args)
    rows.append({"mesh": "pod_share_2d", **row2,
                 "pass": bool(row2["ok"])})
    return rows


def run_knob_validation(dim: int) -> list:
    from repro.core.secure_agg import SecureAggregator
    from repro.kernels.tuning import validate_real_kernel_knobs

    agg = SecureAggregator(backend="pallas")
    reports = validate_real_kernel_knobs(
        d=dim,
        num_residues=agg.scheme.field.num_residues,
        threshold=agg.scheme.threshold,
        num_points=agg.scheme.threshold,
    )
    return [{"check": "real-kernel knobs", **r, "pass": r["ok"]} for r in
            reports]


def main(argv=None):
    args = parse_args(argv)
    if args.child:
        # forced device count already in XLA_FLAGS via mesh_env
        if args.child == "1d":
            row = run_child_1d(args.child_devices, args.params,
                               args.rounds, args.repeats)
        else:
            row = run_child_2d(args.child_devices, args.params,
                               args.repeats)
        print("CHILD_JSON: " + json.dumps(row))
        return row

    if args.quick:
        args.records = 8_000
        args.devices_list = [8, 64]

    rows = run_fit_comparison(args.records, args.institutions, args.dim,
                              args.repeats)
    rows += run_mesh_sweep(args)
    if args.real_kernels:
        rows += run_knob_validation(args.dim)

    out = json.dumps(rows, indent=2)
    print(out)
    path = args.json
    if path is None:
        path = ("BENCH_multihost_rounds_smoke.json" if args.quick
                else "BENCH_multihost_rounds.json")
    if path:
        with open(path, "w") as f:
            f.write(out + "\n")
    return rows


if __name__ == "__main__":
    main()
