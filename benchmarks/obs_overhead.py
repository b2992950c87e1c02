"""Observability tax: traced vs untraced secure fits, bit-parity gated.

The span tracer (``repro.obs.trace``) claims ~zero cost when disabled
and "cheap enough to leave on" when enabled: every span is one
``perf_counter`` pair plus a deque append, all host-side Python around
jitted rounds.  This benchmark pins both claims per driver shape:

* ``loop`` — per-round reference driver (``fused=False``), the chattiest
  shape (most spans per unit work);
* ``fused`` — one jitted graph per round;
* ``scan`` — ``rounds="scan"`` blocks (fewest host transitions, so the
  per-ROUND span cost is amortized across a block).

Gates, per driver shape:

* **overhead** <= 2% per round at the full config (10% under
  ``--quick``, where rounds are too small for a tight timer gate);
* **bit-invisibility** — the traced fit's beta must be BIT-identical to
  the untraced fit's: the tracer may never perturb the protocol.  This
  holds by construction (the in-graph metric leaves are ALWAYS computed;
  tracing only observes host timestamps) and is asserted here.

Timing uses the interleaved-median protocol from fault_overhead.py:
untimed warmups compile everything, then traced/untraced samples run
interleaved with the order flipped every repeat, and the overhead is the
median of per-repeat pairwise ratios — shared-CPU timer drift cancels
instead of reading as fake overhead.

Machine-readable rows land in BENCH_obs_overhead.json (``--quick`` is
the bench_smoke gate size and writes BENCH_obs_overhead_smoke.json).
``--trace-out PREFIX`` additionally exports the final traced run as
PREFIX.jsonl (the run ledger ``results/show.py`` renders) and captures
it under ``jax.profiler`` into the directory PREFIX.profile (open it in
TensorBoard's profile plugin or Perfetto): ``enable(profiler=True)``
puts the spans there as annotations, on the device ops' own clock.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.core import SecureAggregator
from repro.core.newton import SecureFitDriver
from repro.data import generate_synthetic
from repro.obs import trace

VARIANTS = ("loop", "fused", "scan")


def _make_driver(parts, variant: str):
    if variant == "loop":
        return SecureFitDriver(parts, lam=1.0, protect="gradient",
                               fused=False)
    agg = SecureAggregator(backend="pallas")
    if variant == "fused":
        return SecureFitDriver(parts, lam=1.0, protect="gradient",
                               aggregator=agg, fused=True)
    return SecureFitDriver(parts, lam=1.0, protect="gradient",
                           aggregator=agg, fused=True, rounds="scan",
                           rounds_per_sync=4)


def _run_once(parts, variant: str):
    """One full fit; returns (seconds, driver)."""
    driver = _make_driver(parts, variant)
    t0 = time.perf_counter()
    driver.run(max_iter=60)
    return time.perf_counter() - t0, driver


def _sample(parts, variant: str, traced: bool):
    """Min-of-2 per-round seconds under the requested tracing state."""
    if traced:
        trace.enable(capacity=1 << 16)
    else:
        trace.disable()
    try:
        (s1, d1), (s2, _) = (_run_once(parts, variant),
                             _run_once(parts, variant))
        return min(s1, s2) / d1.iteration, d1
    finally:
        trace.disable()


def run(num_institutions: int = 4, dim: int = 64, records: int = 80_000,
        repeats: int = 5, seed: int = 0, full_gate: bool = True,
        trace_out: str | None = None):
    study = generate_synthetic(
        jax.random.PRNGKey(seed), num_institutions=num_institutions,
        records_per_institution=records // num_institutions, dim=dim,
    )
    parts = list(study.parts)
    gate = 2.0 if full_gate else 10.0
    rows = []

    for variant in VARIANTS:
        _run_once(parts, variant)  # warmup: trace + compile + packing
        off_rt, on_rt = [], []
        off_d = on_d = None
        for rep in range(repeats):
            order = (False, True) if rep % 2 == 0 else (True, False)
            for traced in order:
                rt, d = _sample(parts, variant, traced)
                (on_rt if traced else off_rt).append(rt)
                if traced:
                    on_d = d
                else:
                    off_d = d

        overhead_pct = (float(np.median(
            [t / b for t, b in zip(on_rt, off_rt)]
        )) - 1.0) * 100.0
        err = float(np.abs(np.asarray(on_d.beta)
                           - np.asarray(off_d.beta)).max())
        rows.append({
            "driver": variant,
            "institutions": num_institutions, "dim": dim,
            "records": records,
            "rounds": off_d.iteration,
            "seconds_per_round_untraced": min(off_rt),
            "seconds_per_round_traced": min(on_rt),
            "overhead_pct": overhead_pct,
            "gate_pct": gate,
            "beta_err_traced_vs_untraced": err,
            "beta_bit_identical": err == 0.0,
            "pass": overhead_pct <= gate and err == 0.0,
        })
        print(f"{variant:<6} untraced {min(off_rt) * 1e3:8.2f} ms/round  "
              f"traced {min(on_rt) * 1e3:8.2f} ms/round  "
              f"overhead {overhead_pct:+6.2f}% (gate {gate:g}%)  "
              f"bit-identical={err == 0.0}")

    if trace_out:
        # export the LOOP driver: its protect/aggregate/reveal happen as
        # host calls, so the trace shows the whole span taxonomy (the
        # fused/scan graphs keep those phases in-graph under one span)
        tracer = trace.enable(capacity=1 << 16, profiler=True)
        with jax.profiler.trace(f"{trace_out}.profile"):
            _run_once(parts, "loop")
        trace.disable()
        n = tracer.export_jsonl(f"{trace_out}.jsonl")
        print(f"exported {n} spans -> {trace_out}.jsonl; profile -> "
              f"{trace_out}.profile")
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--institutions", type=int, default=4)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--records", type=int, default=80_000,
                    help="total N across all institutions")
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quick", action="store_true",
                    help="small config for the bench_smoke gate "
                         "(S=4, d=32, N=20000, 2 repeats; 10% gate)")
    ap.add_argument("--trace-out", default=None,
                    help="also export a traced loop run as "
                         "PREFIX.jsonl + a profile in PREFIX.profile")
    ap.add_argument("--json", default=None,
                    help="machine-readable output path ('' to skip; "
                         "default BENCH_obs_overhead[_smoke].json)")
    args = ap.parse_args(argv)

    kw = dict(num_institutions=args.institutions, dim=args.dim,
              records=args.records, repeats=args.repeats, seed=args.seed)
    if args.quick:
        kw.update(num_institutions=4, dim=32, records=20_000, repeats=2)
    rows = run(full_gate=not args.quick, trace_out=args.trace_out, **kw)
    rows.append({"config": "quick" if args.quick else "full", **{
        k: kw[k] for k in ("num_institutions", "dim", "records")
    }})

    out = json.dumps(rows, indent=2)
    print(out)
    path = args.json
    if path is None:
        path = ("BENCH_obs_overhead_smoke.json" if args.quick
                else "BENCH_obs_overhead.json")
    if path:
        with open(path, "w") as f:
            f.write(out + "\n")
    if not all(r.get("pass", True) for r in rows):
        raise SystemExit(1)
    return rows


if __name__ == "__main__":
    main()
