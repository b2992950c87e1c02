"""Readings that the limits of ``correct`` are set from, on the chip.

    python bench/calibrate.py --workload <name> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--controls lower,gram] [--out file.jsonl]

For each seed it sets the cell up as a run does, runs jobs through the
program and compares their answers with the float64 reference: the
program's readings, the lower end of each limit.  A ``single_fit`` cell
runs one job for every lambda of the traffic's grid and compares each; a
``cv_path`` cell runs one path and compares one fold fit of every lambda
(folds drawn from the seed) and the refit.  For each control seed it also
puts the reference in the program's place (``reference.CONTROLS``: every
term one rung lower, or the Gram alone), on the chip, and compares those
answers the same way: the upper end.  One JSON line per seed and side.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))


def _single_fit(cell, parts_ref, answers):
    grid = sorted({a.lam for a in answers})
    return {str(lam): cell.compare(answers, parts_ref, [lam])
            for lam in grid}


def _cv_path(cell, parts_ref, answers):
    import numpy as np

    rng = np.random.default_rng(np.random.SeedSequence([cell.seed, 3]))
    k = int(cell.traffic["folds"])
    fits = [(li, int(rng.integers(k)))
            for li in range(len(cell.traffic["lambdas"]))]
    return {"path": cell.compare(answers, parts_ref, fits)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--controls", default="lower")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import importlib

    import jax

    from bench import reference, run

    bench, spec, config, traffic, _ = run.load_cell(args.workload)
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    run.check_device(spec["chips"], peaks)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_enable_x64", True)
    kind = importlib.import_module(f"bench.kinds.{traffic['kind']}")
    path = traffic["kind"] == "cv_path"
    readings = _cv_path if path else _single_fit
    cpu = jax.devices("cpu")[0]
    grid = [float(v) for v in traffic["lambdas"]]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    out = open(args.out, "a") if args.out else None
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        cell = kind.Cell(config, traffic, seed)
        cell.setup()
        t_setup = time.perf_counter() - t0
        t1 = time.perf_counter()
        if path:
            job = next(cell.jobs())
            answers = [cell.run_job(job)]
        else:
            answers = [cell.run_job(lam) for lam in grid]
        job_s = time.perf_counter() - t1
        parts_ref = jax.device_put(cell.parts, cpu)
        sides = [("program", answers)]
        if seed in controls:
            for name in args.controls.split(","):
                t1 = time.perf_counter()
                prec = reference.CONTROLS[name](config["precision"])
                if path:
                    ctrl = [cell.control_answers(job, cell.parts, prec)]
                else:
                    ctrl = cell.control_answers(grid, cell.parts, prec)
                sides.append((f"control-{name}", ctrl))
                print(f"control {name} seconds "
                      f"{time.perf_counter() - t1:.3f}", file=sys.stderr)
        cell.release()
        for side, ans in sides:
            t1 = time.perf_counter()
            per = readings(cell, parts_ref, ans)
            row = {"workload": args.workload, "seed": seed, "side": side,
                   "setup_s": t_setup, "job_s": job_s,
                   "compare_s": time.perf_counter() - t1,
                   "worst": {k: max(p[k] for p in per.values())
                             for k in next(iter(per.values()))},
                   "per": per}
            line = json.dumps(row, default=float)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
        del cell, parts_ref
    return 0


if __name__ == "__main__":
    sys.exit(main())
