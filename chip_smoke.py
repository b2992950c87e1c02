"""Run the secure consortium fit on one TPU chip and check what comes out.

    python chip_smoke.py             # one chip: phases `paper` and `kernels`
    python chip_smoke.py --chips 4   # four chips: phase `collective` only

Phases (each prints its name, cold wall seconds with compilation, and its
checks on one line of JSON):

* ``paper`` — the paper's Synthetic study at paper size (1,000,000 x 6
  over 6 institutions, 2-of-3 Shamir, Hessian and gradient protected),
  driven through ``repro.launch.train.main`` with ``--fused --rounds
  scan``.  The secure beta must reach R^2 >= 0.999999 against the pooled
  ``centralized_fit`` and lie within 1e-6 of it.
* ``kernels`` — a ``StudyCoordinator`` scan fit at S=8 institutions,
  d=128, N=200,000 rows with the f32-Gram summaries rung, so the fused
  IRLS, protect and reveal kernels are all on the path.  Its converged
  beta must lie within the rung's fixed-point contract, (S + 1) / 2**28,
  of ``centralized_fit``, and the compiled round program must hold
  Mosaic kernels (``tpu_custom_call``), not interpreted ones.
* ``collective`` (``--chips 4``) — the SPMD secret-shared all-reduce on
  1e6 f32 parameters: the 1D pod mesh of 4 with the sharded reveal and
  the (2, 2) pod x share mesh, each against ``jax.lax.psum`` of the same
  per-pod trees, and the 2D reveal bitwise against the 1D wire.

The last line of standard output is ``{"ok": true, "device": {...}}``
with the device as JAX reports it.  The script exits non-zero, and
prints no such line, when JAX finds no TPU or any check fails.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))


def _emit(phase: str, seconds: float, checks: dict) -> bool:
    ok = all(bool(v) for k, v in checks.items() if k.endswith("_ok"))
    print(json.dumps({"phase": phase, "seconds": seconds, "ok": ok,
                      **checks}), flush=True)
    return ok


def phase_paper(scale: float = 1.0) -> bool:
    """Paper Synthetic study through the training entry point."""
    from repro.launch import train

    t0 = time.perf_counter()
    out = train.main([
        "--arch", "logreg_paper", "--study", "synthetic", "--scale",
        str(scale), "--protect", "both", "--centers", "3", "--threshold",
        "2", "--fused", "--rounds", "scan",
    ])
    seconds = time.perf_counter() - t0
    return _emit("paper", seconds, {
        "samples": out["samples"],
        "features": out["features"],
        "iterations": out["iterations"],
        "r2_vs_gold": out["r2_vs_gold"],
        "max_abs_err_vs_gold": out["max_abs_err_vs_gold"],
        "converged_ok": out["converged"],
        "r2_ok": out["r2_vs_gold"] >= 0.999999,
        "gold_err_ok": out["max_abs_err_vs_gold"] <= 1e-6,
    })


def phase_kernels(num_institutions: int = 8, dim: int = 128,
                  records: int = 200_000, seed: int = 0) -> bool:
    """f32-Gram coordinator scan fit with every kernel compiled."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import (
        Institution,
        SecureAggregator,
        StudyCoordinator,
        centralized_fit,
    )
    from repro.core.batched_summaries import pack_partitions
    from repro.core.scanfit import fit_scan_block
    from repro.data import generate_synthetic

    t0 = time.perf_counter()
    study = generate_synthetic(
        jax.random.PRNGKey(seed), num_institutions=num_institutions,
        records_per_institution=records // num_institutions, dim=dim,
    )
    agg = SecureAggregator(backend="pallas")
    coord = StudyCoordinator(
        [Institution(f"inst{j}", Xj, yj)
         for j, (Xj, yj) in enumerate(study.parts)],
        lam=1.0, protect="both", aggregator=agg, seed=seed, fused=True,
        rounds="scan", summaries_backend="pallas",
    )
    beta = np.asarray(coord.run())
    fit_seconds = time.perf_counter() - t0
    gold = centralized_fit(*study.pooled(), lam=1.0)
    err = float(np.max(np.abs(beta - gold.beta)))
    bound = (num_institutions + 1) / agg.codec.scale

    # the round program exactly as the coordinator dispatches it
    packed = pack_partitions(list(study.parts))
    rounds = 50
    text = fit_scan_block.lower(
        jnp.zeros((dim,), jnp.float64), jnp.asarray(np.inf),
        jnp.asarray(False), jnp.zeros((), jnp.int32), coord.key,
        jnp.zeros((), jnp.int32), packed.X, packed.X32, packed.y,
        packed.counts, 1.0, agg=agg, protect="both", l1=0.0,
        tol=float(coord.tol),
        points=tuple(c.index for c in coord.live_centers()),
        include_count=True,
        summaries_backend="pallas", num_rounds=rounds,
        num_parts=num_institutions, max_rounds=rounds,
    ).compile().as_text()
    return _emit("kernels", time.perf_counter() - t0, {
        "institutions": num_institutions,
        "features": dim,
        "records": int(packed.total_records),
        "fit_seconds": fit_seconds,
        "iterations": coord.iteration,
        "max_abs_err_vs_gold": err,
        "contract_bound": bound,
        "tpu_custom_calls": text.count("tpu_custom_call"),
        "converged_ok": coord.converged and gold.converged,
        "gold_err_ok": err <= bound,
        "compiled_kernels_ok": "tpu_custom_call" in text,
    })


def phase_collective(params: int = 1_000_000, seed: int = 0) -> bool:
    """SPMD secure all-reduce on four chips vs plain psum."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import SecureAggregator
    from repro.core.collective import secure_psum
    from repro.distributed.multihost import (
        pod_mesh,
        pod_share_mesh,
        secure_psum_2d,
    )
    from repro.distributed.sharding import POD_AXIS

    t0 = time.perf_counter()
    agg = SecureAggregator(backend="pallas")
    key = jax.random.PRNGKey(seed + 1)
    per_pod = 0.01 * jax.random.normal(jax.random.PRNGKey(seed), (4, params),
                                       jnp.float32)
    mesh1 = pod_mesh(4)
    mesh2 = pod_share_mesh(2, agg.scheme.threshold)

    def spmd(mesh, body):
        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=P(POD_AXIS), out_specs=P(),
            check_vma=False,
        ))

    secure_1d = spmd(mesh1, lambda x: secure_psum(
        {"g": x[0]}, POD_AXIS, key, aggregator=agg, reveal="sharded")["g"])
    secure_2d = spmd(mesh2, lambda x: secure_psum_2d(
        {"g": x[0]}, key, aggregator=agg)["g"])
    plain_1d = spmd(mesh1, lambda x: jax.lax.psum(x[0], POD_AXIS))
    plain_2d = spmd(mesh2, lambda x: jax.lax.psum(x[0], POD_AXIS))

    x4 = jax.device_put(per_pod, NamedSharding(mesh1, P(POD_AXIS)))
    x2 = jax.device_put(per_pod[:2], NamedSharding(mesh2, P(POD_AXIS)))
    # the first two pods' trees plus two zero trees: the field sum of the
    # 1D wire then equals the 2D mesh's exactly (zero encodes to zero)
    x4_pad = jax.device_put(
        jnp.concatenate([per_pod[:2], jnp.zeros_like(per_pod[2:])]),
        NamedSharding(mesh1, P(POD_AXIS)),
    )
    s1, p1 = secure_1d(x4), plain_1d(x4)
    s2, p2 = secure_2d(x2), plain_2d(x2)
    s1_pad = secure_1d(x4_pad)
    jax.block_until_ready((s1, p1, s2, p2, s1_pad))

    def tol(pods, total):
        # fixed-point rounding of each pod's encode and of the reveal,
        # plus the f32 rounding of the plain psum itself
        f32 = pods * float(np.finfo(np.float32).eps) * float(
            np.max(np.abs(total)))
        return (pods + 1) / agg.codec.scale + f32

    err1 = float(np.max(np.abs(np.asarray(s1, np.float64)
                               - np.asarray(p1, np.float64))))
    err2 = float(np.max(np.abs(np.asarray(s2, np.float64)
                               - np.asarray(p2, np.float64))))
    devices = {d.id for d in jax.devices()}
    return _emit("collective", time.perf_counter() - t0, {
        "params": params,
        "mesh_1d": dict(mesh1.shape),
        "mesh_2d": dict(mesh2.shape),
        "max_abs_err_1d_vs_psum": err1,
        "max_abs_err_2d_vs_psum": err2,
        "mesh_1d_spans_4_ok": {d.id for d in mesh1.devices.flat} == devices
        and len(devices) == 4,
        "mesh_2d_spans_4_ok": {d.id for d in mesh2.devices.flat} == devices,
        "input_on_4_ok": len({s.device.id for s in x4.addressable_shards})
        == 4,
        "secure_1d_ok": err1 <= tol(4, np.asarray(p1)),
        "secure_2d_ok": err2 <= tol(2, np.asarray(p2)),
        "bitwise_2d_vs_1d_ok": bool(np.array_equal(np.asarray(s2),
                                                   np.asarray(s1_pad))),
    })


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the paper and kernels phases on one chip; "
                         "4: the cross-chip collective phase only")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    phases = [phase_collective] if args.chips == 4 else [phase_paper,
                                                         phase_kernels]
    ok = True
    for phase in phases:
        try:
            ok = phase() and ok
        except Exception as e:  # a failed phase fails the run, loudly
            traceback.print_exc()
            print(json.dumps({"phase": phase.__name__[len("phase_"):],
                              "ok": False,
                              "error": f"{type(e).__name__}: {e}"}),
                  flush=True)
            ok = False
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
