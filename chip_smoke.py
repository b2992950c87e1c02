"""Run the secure consortium fit on one TPU chip and check what comes out.

    python chip_smoke.py             # one chip: `paper`, `kernels`, `sliced`
    python chip_smoke.py --chips 4   # four chips: `collective`, `pod_fit`

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
* ``sliced`` — the float64 gradient and deviance terms from bf16 slices
  of X (``repro.kernels.sliced_terms``) at d=128 on 64 ragged sites,
  against numpy float64: the slices cut on the chip must give the chip's
  X back to 2**-55 of each row's largest entry, every f32 partial of the
  MXU dots must be an integer under 2**24 (an MXU that did not
  accumulate them exactly would show here first), and z, g and dev must
  lie within a float64 dot's error bound.  64 sites, because XLA fused
  the float64 level sum into the dot there and not at 16.
* ``collective`` (``--chips 4``) — the SPMD secret-shared all-reduce on
  1e6 f32 parameters: the 1D pod mesh of 4 with the sharded reveal and
  the (2, 2) pod x share mesh, each against ``jax.lax.psum`` of the same
  per-pod trees, and the 2D reveal bitwise against the 1D wire.
* ``pod_fit`` (``--chips 4``) — ``StudyCoordinator(pods=4)`` at 16 ragged
  sites, d=128, the ``pallas`` rung with the slices of X: four sites a
  chip, the pods' shares summed on the exact cross-chip wire.  Same
  rounds and the same beta, to 1e-12 of its largest entry, as the fit
  of the same sites on one chip; within the fixed-point contract of
  ``centralized_fit``; the compiled pod program holds the kernels and
  one all-reduce over the four chips, under ``pod_wire``.

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
    packed = pack_partitions(list(study.parts), backend="pallas")
    rounds = 50
    text = fit_scan_block.lower(
        jnp.zeros((dim,), jnp.float64), jnp.asarray(np.inf),
        jnp.asarray(False), jnp.zeros((), jnp.int32), coord.key,
        jnp.zeros((), jnp.int32), packed.X, packed.X32, packed.slices,
        packed.y, packed.counts, 1.0, agg=agg, protect="both", l1=0.0,
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


def phase_sliced(sizes=(1, 900, 3000) + (3760,) * 61, dim: int = 128,
                 seed: int = 0) -> bool:
    """Sliced float64 terms on the chip against numpy float64."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro.core  # noqa: F401  (float64 on)
    from repro.kernels.sliced_terms import (
        K, cut_slices, g_levels, sliced_terms, z_levels)

    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    n = max(sizes)
    X = rng.standard_normal((len(sizes), n, dim))
    X[..., 0] = 1.0  # the intercept
    X[2, 5] *= 2.0 ** 20  # rows far from the others in size
    X[2, 6] *= 2.0 ** -20
    X[3, 7] = 0.0
    y = (rng.random((len(sizes), n)) < 0.5).astype(np.float64)
    counts = np.asarray(sizes, np.int32)
    for s, m in enumerate(sizes):
        X[s, m:] = 0.0
        y[s, m:] = 0.0
    beta = rng.uniform(-0.2, 0.2, dim)
    slices = cut_slices(jnp.asarray(X))
    _, g, dev = jax.jit(sliced_terms)(jnp.asarray(beta), slices,
                                      jnp.asarray(y), jnp.asarray(counts))
    zl, bscale = jax.jit(z_levels)(jnp.asarray(beta), slices.digits)
    q = rng.uniform(-1.0, 1.0, slices.scale.shape) * np.asarray(
        slices.scale)
    gl, _ = jax.jit(g_levels)(jnp.asarray(q), slices.digits)
    digits = np.asarray(slices.digits, np.float64)
    scale = np.asarray(slices.scale)
    back = sum(digits[..., i * dim:(i + 1) * dim] * 2.0 ** (-8 * (i + 1))
               for i in range(K)) * scale[..., None]
    # against X as the chip holds it: a TPU keeps float64 as a pair of
    # f32, some 48 bits, so the numpy X does not survive the transfer
    X_chip = np.asarray(jnp.asarray(X))
    row_max = np.abs(X_chip).max(axis=2)
    cut_err = float(np.max(np.abs(back[:, :n] - X_chip).max(axis=2)
                           / np.where(row_max > 0, row_max, 1.0)))
    partials = [np.asarray(zl, np.float64), np.asarray(gl, np.float64)]

    u = 2.0 ** -53
    mask = (np.arange(n)[None, :] < counts[:, None]).astype(np.float64)
    z = np.einsum("snd,d->sn", X, beta)
    zfix = sum(partials[0][lv] * 2.0 ** (-8 * (lv + 2)) for lv in range(K))
    got_z = (zfix * scale * float(bscale))[:, :n]
    p = 1.0 / (1.0 + np.exp(-z))
    r = (y - p) * mask
    ll = (y * z - np.logaddexp(0.0, z)) * mask
    xb = np.einsum("snd,d->sn", np.abs(X), np.abs(beta))
    bound_g = n * u * np.einsum("snd,sn->sd", np.abs(X), np.abs(r))
    bound_dev = 2.0 * u * (n * np.abs(ll).sum(axis=1)
                           + dim * (xb * mask).sum(axis=1))

    def worst(err, bound):
        return float(np.max(err / np.where(bound > 0, bound, 1.0)))

    z_ratio = worst(np.abs(got_z - z), dim * u * xb)
    g_ratio = worst(np.abs(np.asarray(g) - np.einsum("snd,sn->sd", X, r)),
                    bound_g)
    dev_ratio = worst(np.abs(np.asarray(dev) + 2.0 * ll.sum(axis=1)),
                      bound_dev)
    return _emit("sliced", time.perf_counter() - t0, {
        "sites": len(sizes),
        "rows": n,
        "features": dim,
        "cut_err_over_row_max": cut_err,
        "z_err_over_bound": z_ratio,
        "g_err_over_bound": g_ratio,
        "dev_err_over_bound": dev_ratio,
        "digits_ok": bool(np.all(digits == np.round(digits))
                          and np.abs(digits).max() <= 128),
        "cut_ok": cut_err <= 2.0 ** -55,
        "partials_ok": all(bool(np.all(v == np.round(v))
                                and np.abs(v).max() < 2.0 ** 24)
                           for v in partials),
        "z_ok": z_ratio <= 1.0,
        "g_ok": g_ratio <= 1.0,
        "dev_ok": dev_ratio <= 1.0,
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


def phase_pod_fit(sizes=(9000, 1200, 7000, 300, 8000, 5000, 2500, 6000,
                          4000, 7500, 650, 3000, 8500, 1800, 5500, 4200),
                  dim: int = 128, seed: int = 0) -> bool:
    """The fit with its sites sharded over four chips vs one chip."""
    import re

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
    from repro.core.scanfit import fit_scan_block, pod_partitioner
    from repro.distributed.multihost import pod_mesh
    from repro.obs import metrics

    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    beta_true = rng.uniform(-0.1, 0.1, dim)
    parts = []
    for n in sizes:
        X = np.concatenate([np.ones((n, 1)),
                            rng.standard_normal((n, dim - 1))], axis=1)
        y = rng.random(n) < 1.0 / (1.0 + np.exp(-X @ beta_true))
        parts.append((jnp.asarray(X), jnp.asarray(y, jnp.float64)))
    agg = SecureAggregator(backend="pallas")

    def fit(pods):
        coord = StudyCoordinator(
            [Institution(f"site{j}", X, y) for j, (X, y) in enumerate(parts)],
            lam=1.0, protect="both", aggregator=agg, seed=seed, fused=True,
            rounds="scan", summaries_backend="pallas", pods=pods,
        )
        t = time.perf_counter()
        beta = np.asarray(coord.run())
        return coord, beta, time.perf_counter() - t

    one, b1, one_s = fit(1)
    metrics.reset()
    four, b4, four_s = fit(4)
    wire = metrics.get("repro_pod_wire_bytes_total")
    gold = centralized_fit(jnp.concatenate([X for X, _ in parts]),
                           jnp.concatenate([y for _, y in parts]), lam=1.0)
    gap = float(np.max(np.abs(b4 - b1)) / np.max(np.abs(b1)))
    err = float(np.max(np.abs(b4 - np.asarray(gold.beta))))
    bound = (len(sizes) + 1) / agg.codec.scale

    # the pod program as the coordinator dispatches it
    mesh = pod_mesh(4)
    packed = pack_partitions(parts, backend="pallas", mesh=mesh)
    with pod_partitioner(mesh):
        text = fit_scan_block.lower(
            jnp.zeros((dim,), jnp.float64), jnp.asarray(np.inf),
            jnp.asarray(False), jnp.zeros((), jnp.int32), four.key,
            jnp.zeros((), jnp.int32), packed.X, packed.X32, packed.slices,
            packed.y, packed.counts, 1.0, agg=agg, protect="both", l1=0.0,
            tol=float(four.tol),
            points=tuple(c.index for c in four.live_centers()),
            include_count=True, summaries_backend="pallas", num_rounds=50,
            num_parts=len(sizes), max_rounds=50, mesh=mesh,
        ).compile().as_text()
    reduces = [line for line in text.splitlines()
               if re.search(r" all-reduce(-start)?\(", line)]
    return _emit("pod_fit", time.perf_counter() - t0, {
        "sites": len(sizes),
        "rows": sum(sizes),
        "features": dim,
        "rounds": [one.iteration, four.iteration],
        "fit_seconds": [one_s, four_s],
        "beta_gap_over_max": gap,
        "max_abs_err_vs_gold": err,
        "contract_bound": bound,
        "wire_bytes": wire,
        "sites_per_chip": [int(s.data.shape[0])
                           for s in packed.X.addressable_shards],
        "slices_ok": packed.slices is not None,
        "converged_ok": one.converged and four.converged,
        "rounds_ok": one.iteration == four.iteration,
        "beta_ok": gap <= 1e-12,
        "gold_err_ok": err <= bound,
        "wire_bytes_ok": wire == four.iteration * agg.pod_wire_bytes(
            dim, include_count=True),
        "one_all_reduce_ok": len(reduces) == 1
        and "/aggregate/pod_wire/" in reduces[0],
        "compiled_kernels_ok": "tpu_custom_call" in text,
    })


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the paper, kernels and sliced phases on "
                         "one chip; 4: the cross-chip collective and pod "
                         "fit phases")
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
    phases = [phase_collective, phase_pod_fit] if args.chips == 4 else [
        phase_paper, phase_kernels, phase_sliced]
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
