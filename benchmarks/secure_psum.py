"""In-SPMD secure_psum: flat-buffer sharded wire vs the old per-leaf tree.

Measures, for a gradient-sized float32 pytree all-reduced securely over a
D-device "pod" (institution) axis:

* **payload bytes per device, from static shapes alone** — the number the
  acceptance gate rides on.  Ring-collective accounting: an all-reduce of
  a B-byte buffer moves ``2 * B * (D-1)/D`` per device (reduce-scatter +
  all-gather phases), a lone reduce-scatter or all-gather ``B * (D-1)/D``.
  Share payloads are counted at their *wire dtype*: uint32 for the flat
  tile buffer (a deployment fabric reduces shares with per-hop modular
  adds, so reduced 31-bit residues travel in 4 bytes; the in-graph jax
  simulation widens to uint64 only because XLA's psum has no per-hop mod
  — see ``check_aggregation_headroom``), uint64 for the old per-leaf tree
  whose share tensors ARE uint64.
* **wall clock** — min-of-repeats of the jitted shard_map program on D
  forced host devices (one CPU underneath: structure, not fabric speed).
* **exactness** — every revealed aggregate vs the float64 sum.

Paths:

* ``plain``           — jax.lax.psum of the float tree (no privacy).
* ``per_leaf``        — frozen replica of the pre-PR secure_psum: per-leaf
                        reference protect, psum of the FULL (w, R, ...)
                        uint64 share tree, reconstruction from all w
                        points on every device.  The baseline the ISSUE
                        gate compares against (kept inline so library
                        changes cannot silently move it).
* ``flat_replicated`` — secure_psum on the flat-buffer wire: one packed
                        (rows, 128) buffer, fused encode+share of ONLY
                        the t reveal points, one uint32-wire psum, fused
                        Lagrange+CRT reveal on every device.
* ``flat_sharded``    — secure_psum(reveal="sharded"): reduce-scatter of
                        the share buffer over the pod axis (each device
                        holds 1/D of the distributed residues), local
                        reveal of the row tile, all-gather of the decoded
                        float aggregate.

Acceptance (ISSUE 5): at 1e6 params the sharded flat wire must transmit
<= 0.55x the per-leaf payload with revealed aggregates matching the
reference oracle within quantization tolerance.  Writes
BENCH_secure_psum.json (or BENCH_secure_psum_smoke.json under --quick;
scripts/bench_smoke.sh runs the quick gate standing).
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--params", type=int, default=1_000_000,
                    help="elements in the gradient tree (acceptance: 1e6)")
    ap.add_argument("--devices", type=int, default=4,
                    help="forced host device count = pod axis size")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--quick", action="store_true",
                    help="smoke scale (1e5 params, 2 repeats) and the "
                         "smoke JSON filename")
    ap.add_argument("--json", default=None,
                    help="output path (default BENCH_secure_psum.json, "
                         "smoke name under --quick; '' to skip)")
    return ap.parse_args(argv)


def _payload_rows(params: int, devices: int, agg, dtype_bytes: int = 4):
    """Static per-device wire-byte model for every path (see module doc)."""
    from repro.core.flatbuf import LANES, ROW_ALIGN, _rows_for

    scheme = agg.scheme
    w, t = scheme.num_shares, scheme.threshold
    num_r = scheme.field.num_residues
    ring = (devices - 1) / devices if devices > 1 else 1.0
    rows = _rows_for(params, ROW_ALIGN)
    rows_sharded = _rows_for(params, math.lcm(ROW_ALIGN, devices))
    flat_buf = num_r * rows * LANES * 4  # uint32 wire, t slices travel
    flat_buf_sharded = num_r * rows_sharded * LANES * 4
    return {
        "plain": 2 * params * dtype_bytes * ring,
        "per_leaf": 2 * w * num_r * params * 8 * ring,  # uint64 share tree
        "flat_replicated": 2 * t * flat_buf * ring,
        "flat_sharded": (t * flat_buf_sharded  # reduce-scatter, one way
                         + rows_sharded * LANES * dtype_bytes) * ring,
    }


def run(params: int, devices: int, repeats: int):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.core.secure_agg import SecureAggregator, secure_psum
    from repro.distributed.sharding import POD_AXIS

    agg_pal = SecureAggregator(backend="pallas")
    agg_ref = SecureAggregator(backend="reference")
    key = jax.random.PRNGKey(0)
    tree = {"g": 0.01 * jax.random.normal(key, (params,), jnp.float32)}
    gold = devices * np.asarray(tree["g"], np.float64)
    mesh = jax.make_mesh((devices,), (POD_AXIS,))
    psum_key = jax.random.PRNGKey(7)

    def spmd(fn):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(),
                                     out_specs=P(), check_vma=False))

    def per_leaf_frozen():
        """Pre-PR secure_psum, frozen: full uint64 tree, all-w reveal."""
        idx = jax.lax.axis_index(POD_AXIS)
        k = jax.random.fold_in(psum_key, idx)
        protected = agg_ref.protect(k, tree)

        def field_psum(shares):
            summed = jax.lax.psum(shares.astype(jnp.uint64), POD_AXIS)
            p = agg_ref.scheme.field.moduli_array().reshape(
                (1, agg_ref.scheme.field.num_residues)
                + (1,) * (shares.ndim - 2)
            )
            return (summed % p).astype(shares.dtype)

        aggregated = jax.tree_util.tree_map(field_psum, protected)
        w = agg_ref.scheme.num_shares
        recon = agg_ref.scheme.reconstruct_pytree(
            aggregated, list(range(1, w + 1))
        )
        return jax.tree_util.tree_map(
            lambda v: agg_ref.codec.decode(v, dtype=jnp.float32), recon
        )

    fns = {
        "plain": spmd(lambda: jax.lax.psum(tree, POD_AXIS)),
        "per_leaf": spmd(per_leaf_frozen),
        "flat_replicated": spmd(lambda: secure_psum(
            tree, POD_AXIS, psum_key, aggregator=agg_pal,
            reveal="replicated")),
        "flat_sharded": spmd(lambda: secure_psum(
            tree, POD_AXIS, psum_key, aggregator=agg_pal,
            reveal="sharded")),
    }
    payload = _payload_rows(params, devices, agg_pal)
    quant_tol = (devices + 1) * 0.5 / agg_pal.codec.scale

    rows = []
    timings, outs = {}, {}
    for name, fn in fns.items():
        out = fn()
        jax.block_until_ready(out)  # warmup: trace + compile off the clock
        best = 1e30
        for _ in range(repeats):
            t0 = time.perf_counter()
            out = fn()
            jax.block_until_ready(out)
            best = min(best, time.perf_counter() - t0)
        timings[name], outs[name] = best, out
        err = float(np.max(np.abs(np.asarray(out["g"], np.float64) - gold)))
        rows.append({
            "path": name,
            "params": params,
            "devices": devices,
            "seconds": best,
            "payload_bytes_per_device": int(payload[name]),
            "max_abs_err": err,
            "quantization_tol": quant_tol,
            "pass": err <= (1e-6 if name == "plain" else quant_tol),
        })

    # the secure paths must agree with each other bit-for-bit: same codec,
    # exact field arithmetic, only the wire differs
    flat_vs_oracle = float(np.max(np.abs(
        np.asarray(outs["flat_sharded"]["g"], np.float64)
        - np.asarray(outs["per_leaf"]["g"], np.float64)
    )))
    rows.append({
        "check": "sharded payload vs per_leaf",
        "per_leaf_payload_bytes": int(payload["per_leaf"]),
        "flat_replicated_payload_bytes": int(payload["flat_replicated"]),
        "flat_sharded_payload_bytes": int(payload["flat_sharded"]),
        "replicated_ratio": payload["flat_replicated"] / payload["per_leaf"],
        "sharded_ratio": payload["flat_sharded"] / payload["per_leaf"],
        "max_abs_err_vs_oracle": flat_vs_oracle,
        "pass": (payload["flat_sharded"] / payload["per_leaf"] <= 0.55
                 and flat_vs_oracle == 0.0),
    })
    rows.append({
        "check": "sharded wallclock vs per_leaf",
        "per_leaf_seconds": timings["per_leaf"],
        "flat_replicated_seconds": timings["flat_replicated"],
        "flat_sharded_seconds": timings["flat_sharded"],
        "plain_seconds": timings["plain"],
        "speedup": timings["per_leaf"] / max(timings["flat_sharded"], 1e-12),
        "secure_overhead_vs_plain": timings["flat_sharded"]
        / max(timings["plain"], 1e-12),
        "pass": timings["per_leaf"]
        / max(timings["flat_sharded"], 1e-12) >= 1.0,
    })
    return rows


def main(argv=None):
    args = parse_args(argv)
    # the forced device count must be owned before jax initializes
    flags = os.environ.get("XLA_FLAGS", "")
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={args.devices}"
    ).strip()
    if "jax" in sys.modules:
        raise SystemExit("secure_psum benchmark must own jax init "
                         "(run as a script, not after importing jax)")
    params = 100_000 if args.quick else args.params
    repeats = min(args.repeats, 2) if args.quick else args.repeats
    rows = run(params, args.devices, repeats)
    out = json.dumps(rows, indent=2)
    print(out)
    path = args.json
    if path is None:
        path = ("BENCH_secure_psum_smoke.json" if args.quick
                else "BENCH_secure_psum.json")
    if path:
        with open(path, "w") as f:
            f.write(out + "\n")
    return rows


if __name__ == "__main__":
    main()
