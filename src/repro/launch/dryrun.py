"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this script jits the real entry point (train_step /
prefill / serve_step) against ShapeDtypeStruct inputs with the production
shardings, compiles it for the 16x16 (single-pod) or 2x16x16 (multi-pod)
mesh of placeholder CPU devices, and records:

  * compiled.memory_analysis()  — proves the cell fits per-device HBM
  * compiled.cost_analysis()    — XLA's own (loop-unaware) numbers
  * launch.hlo_analysis         — trip-count-aware flops/bytes/collectives

Results go to results/dryrun/<arch>__<shape>__<mesh>.json; EXPERIMENTS.md
§Dry-run and benchmarks/roofline.py read them.

Usage:
  python -m repro.launch.dryrun --arch qwen2_72b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--jobs 2]
"""
import argparse
import json
import os
import subprocess
import sys
import time

from repro.distributed.xla_flags import apply_xla_flags


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=["train_4k", "prefill_32k", "decode_32k",
                             "long_500k"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) as subprocesses")
    ap.add_argument("--jobs", type=int, default=2)
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--host-devices", type=int, default=512,
                    help="placeholder device count (tests use fewer)")
    ap.add_argument("--mesh-shape", default=None,
                    help="override mesh, e.g. '2,2' or '2,2,2' (tests)")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (tests)")
    ap.add_argument("--variant", default="baseline",
                    help="perf variant tag recorded in the result")
    ap.add_argument("--microbatch", type=int, default=1,
                    help="gradient-accumulation microbatches for train "
                         "cells (activations scale with B/n)")
    ap.add_argument("--optimized", action="store_true",
                    help="apply the per-(arch, shape) §Perf preset "
                         "(configs/perf_presets.py)")
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=VALUE", dest="overrides",
                    help="dataclasses.replace override on the model config "
                         "(int/float/str auto-coerced); repeatable")
    return ap.parse_args(argv)


ARGS = parse_args()
# ONE validated flag write, before the first jax use: apply_xla_flags
# raises if a backend already locked the device count (the old two-write
# shape set a module-level default and then silently overwrote it after
# parse_args, trusting nothing had initialized jax in between).
apply_xla_flags(host_device_count=ARGS.host_devices)
# The placeholder devices are CPU devices, here and in the --all
# children that inherit this environment: none of them may claim an
# accelerator.
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402  (after XLA_FLAGS)
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro.core  # noqa: E402  (x64 for the secure-agg variant)
from repro.configs import ARCH_IDS, get_config, smoke_config  # noqa: E402
from repro.distributed import MeshRules  # noqa: E402
from repro.launch.hlo_analysis import analyze_hlo  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch import specs as SP  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.models.config import SHAPES  # noqa: E402
from repro.optim.adamw import AdamWConfig, adamw_update  # noqa: E402

LM_ARCHS = tuple(a for a in ARCH_IDS if a != "logreg_paper")


def make_mesh():
    from repro.distributed.sharding import make_mesh as _make_mesh

    if ARGS.mesh_shape:
        dims = tuple(int(x) for x in ARGS.mesh_shape.split(","))
        axes = ("pod", "data", "model")[-len(dims):]
        return _make_mesh(dims, axes)
    return make_production_mesh(multi_pod=ARGS.multi_pod)


def lower_cell(cfg, shape, mesh):
    """Returns (lowered, compiled, timings) for one cell."""
    rules = MeshRules(mesh=mesh)
    inputs = SP.input_specs(cfg, shape)
    t0 = time.time()
    if shape.kind == "train":
        params_abs, p_sh, opt_abs, opt_sh = SP.train_state_specs(cfg, rules)
        b_sh = SP.batch_shardings(inputs, rules)
        opt_cfg = AdamWConfig()

        n_micro = max(ARGS.microbatch,
                      getattr(cfg, "train_microbatch", 1))

        def train_step(params, opt_state, batch):
            if n_micro <= 1:
                (loss, metrics), grads = jax.value_and_grad(
                    T.loss_fn, has_aux=True
                )(params, batch, cfg, rules)
            else:
                # gradient accumulation: scan over microbatches so the
                # remat-saved activations scale with B/n_micro, not B —
                # what makes the deepest/widest train cells fit HBM.
                def slice_mb(x):
                    B = x.shape[0]
                    return x.reshape((n_micro, B // n_micro) + x.shape[1:])

                mb_batch = jax.tree_util.tree_map(slice_mb, batch)

                def _gconstrain(g):
                    # keep the f32 accumulator sharded like the params —
                    # without this XLA replicates the carry (measured:
                    # +100 GB temp on the 32B/72B fsdp cells)
                    return jax.tree_util.tree_map(
                        lambda z, sh: (
                            jax.lax.with_sharding_constraint(z, sh)
                            if sh is not None else z
                        ), g, p_sh,
                    )

                def mb_step(carry, mb):
                    gacc, lacc = carry
                    (l, m), g = jax.value_and_grad(
                        T.loss_fn, has_aux=True
                    )(params, mb, cfg, rules)
                    gacc = jax.tree_util.tree_map(
                        lambda a, b: a + b.astype(jnp.float32), gacc, g
                    )
                    # only g0 carries an explicit constraint; the carry
                    # keeps its sharding by propagation (verified: a
                    # per-iteration constraint changes nothing — the
                    # measured microbatch collective overhead is the per-
                    # microbatch gradient reductions themselves).
                    return (gacc, lacc + l), m

                g0 = _gconstrain(jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params
                ))
                (gacc, lsum), ms = jax.lax.scan(
                    mb_step, (g0, jnp.zeros((), jnp.float32)), mb_batch
                )
                grads = jax.tree_util.tree_map(
                    lambda g: g / n_micro, gacc
                )
                loss = lsum / n_micro
                metrics = jax.tree_util.tree_map(lambda m: m[-1], ms)
            new_p, new_o, om = adamw_update(grads, opt_state, params,
                                            opt_cfg)
            return new_p, new_o, {**metrics, **om, "loss": loss}

        lowered = jax.jit(
            train_step,
            in_shardings=(p_sh, opt_sh, b_sh),
            out_shardings=(p_sh, opt_sh, None),
        ).lower(params_abs, opt_abs, inputs)
    elif shape.kind == "prefill":
        params_abs, p_sh, _, _ = SP.train_state_specs(cfg, rules)
        b_sh = SP.batch_shardings(inputs, rules)
        cache_abs = jax.eval_shape(
            lambda: T.init_cache(cfg, shape.global_batch, shape.seq_len)
        )
        c_sh = SP.cache_pspecs(cache_abs, rules, cfg)
        logits_sh = rules.sharding(
            rules.dp_axes if shape.global_batch % rules.dp_size == 0
            else None,
            rules.tp_axis if cfg.vocab_size % rules.tp_size == 0 else None,
        )

        def prefill_step(params, batch):
            return T.prefill(params, cfg, rules,
                             tokens=batch.get("tokens"),
                             embeds=batch.get("embeds"))

        lowered = jax.jit(
            prefill_step,
            in_shardings=(p_sh, b_sh),
            out_shardings=(logits_sh, c_sh, None),
        ).lower(params_abs, inputs)
    else:  # decode
        params_abs, p_sh, _, _ = SP.train_state_specs(cfg, rules)
        caches = inputs["caches"]
        c_sh = SP.cache_pspecs(caches, rules, cfg)
        tok_sh = SP.batch_shardings(
            {k: v for k, v in inputs.items()
             if k in ("tokens", "embeds")}, rules
        )
        logits_sh = rules.sharding(
            rules.dp_axes if shape.global_batch % rules.dp_size == 0
            else None,
            rules.tp_axis if cfg.vocab_size % rules.tp_size == 0 else None,
        )

        def serve_step(params, caches, length, batch):
            return T.decode_step(params, caches, length, cfg, rules,
                                 tokens=batch.get("tokens"),
                                 embeds=batch.get("embeds"))

        batch = {k: v for k, v in inputs.items()
                 if k in ("tokens", "embeds")}
        lowered = jax.jit(
            serve_step,
            in_shardings=(p_sh, c_sh, None, tok_sh),
            out_shardings=(logits_sh, c_sh, None),
        ).lower(params_abs, caches, inputs["length"], batch)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    return lowered, compiled, {"lower_s": t_lower, "compile_s": t_compile}


def run_cell(arch: str, shape_name: str):
    cfg = smoke_config(arch) if ARGS.smoke else get_config(arch)
    shape = SHAPES[shape_name]
    if ARGS.optimized:
        from repro.configs.perf_presets import apply_preset
        cfg = apply_preset(cfg, shape)
    if ARGS.overrides:
        import dataclasses
        kv = {}
        for item in ARGS.overrides:
            key, val = item.split("=", 1)
            field_t = type(getattr(cfg, key))
            kv[key] = field_t(val) if field_t is not bool else val == "True"
        cfg = dataclasses.replace(cfg, **kv)
    mesh = make_mesh()
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "axes": list(mesh.axis_names),
        "devices": int(np.prod(mesh.devices.shape)),
        "variant": ARGS.variant,
        "overrides": ARGS.overrides,
        "smoke": ARGS.smoke,
    }
    if shape_name == "long_500k" and not cfg.sub_quadratic:
        result["skipped"] = (
            "pure full-attention arch: 512k dense decode excluded per "
            "DESIGN.md §Arch-applicability"
        )
        return result
    lowered, compiled, times = lower_cell(cfg, shape, mesh)
    result.update(times)
    mem = compiled.memory_analysis()
    result["memory"] = {
        "argument_bytes_per_device": int(mem.argument_size_in_bytes),
        "output_bytes_per_device": int(mem.output_size_in_bytes),
        "temp_bytes_per_device": int(mem.temp_size_in_bytes),
        "alias_bytes_per_device": int(mem.alias_size_in_bytes),
        "code_bytes": int(mem.generated_code_size_in_bytes),
    }
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):  # jax 0.4.x: one dict per device
        ca = ca[0] if ca else {}
    result["xla_cost_analysis"] = {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "note": "loop bodies counted once by XLA (see hlo_analysis)",
    }
    t0 = time.time()
    hlo_text = compiled.as_text()
    if not ARGS.smoke:
        import gzip
        os.makedirs(ARGS.out, exist_ok=True)
        mesh_tag = "multipod" if ARGS.multi_pod else "singlepod"
        if ARGS.variant != "baseline":
            mesh_tag += f"__{ARGS.variant}"
        hlo_path = os.path.join(
            ARGS.out, f"{arch}__{shape_name}__{mesh_tag}.hlo.gz"
        )
        with gzip.open(hlo_path, "wt") as f:
            f.write(hlo_text)
    hlo = analyze_hlo(hlo_text)
    result["hlo_analysis"] = {
        "flops_per_device": hlo.flops,
        "bytes_per_device": hlo.bytes,
        "collective_bytes_per_device": dict(hlo.collective_bytes),
        "collective_counts": dict(hlo.collective_count),
        "bytes_by_kind": dict(hlo.bytes_by_kind),
        "top_byte_buckets": [
            {"bucket": k, "bytes": v} for k, v in hlo.top_buckets()
        ],
        "analysis_s": time.time() - t0,
    }
    result["model"] = {
        "params": T.count_params(cfg),
        "active_params": T.count_params(cfg, active_only=True),
    }
    return result


def main():
    os.makedirs(ARGS.out, exist_ok=True)
    mesh_tag = "multipod" if ARGS.multi_pod else "singlepod"
    if ARGS.all:
        cells = [(a, s) for a in LM_ARCHS for s in SHAPES]
        procs: list = []
        failures = []

        def drain(block_all=False):
            while procs and (block_all or len(procs) >= ARGS.jobs):
                for i, (p, cell) in enumerate(procs):
                    if p.poll() is not None:
                        if p.returncode != 0:
                            failures.append(cell)
                            print(f"FAIL {cell}", flush=True)
                        procs.pop(i)
                        break
                else:
                    time.sleep(1.0)

        for arch, shape in cells:
            tag = mesh_tag if ARGS.variant == "baseline" else (
                f"{mesh_tag}__{ARGS.variant}"
            )
            out_file = os.path.join(
                ARGS.out, f"{arch}__{shape}__{tag}.json"
            )
            if os.path.exists(out_file):
                print(f"skip (exists): {out_file}", flush=True)
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--out", ARGS.out,
                   "--variant", ARGS.variant]
            for item in ARGS.overrides:
                cmd += ["--set", item]
            if ARGS.optimized:
                cmd.append("--optimized")
            if ARGS.multi_pod:
                cmd.append("--multi-pod")
            if ARGS.smoke:
                cmd.append("--smoke")
            drain()
            print(f"launch: {arch} {shape} {mesh_tag}", flush=True)
            procs.append((subprocess.Popen(cmd), (arch, shape)))
        drain(block_all=True)
        print(f"done; {len(failures)} failures: {failures}", flush=True)
        sys.exit(1 if failures else 0)

    assert ARGS.arch and ARGS.shape, "--arch and --shape (or --all)"
    result = run_cell(ARGS.arch, ARGS.shape)
    tag = mesh_tag if ARGS.variant == "baseline" else (
        f"{mesh_tag}__{ARGS.variant}"
    )
    out_file = os.path.join(
        ARGS.out, f"{ARGS.arch}__{ARGS.shape}__{tag}.json"
    )
    with open(out_file, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result, indent=2))


if __name__ == "__main__":
    main()
