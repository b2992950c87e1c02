"""The on-chip benchmark: one cell of ``BENCHMARK.json``, one run.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the checkout's root.  The cell names a configuration (its file of
sizes under ``bench/configs``) and a traffic mix (``bench/traffic/<name>
.json``, whose ``kind`` names the job driver in ``bench/kinds``).  A run:

1. refuses anything but a TPU whose ``device_kind`` is in
   ``bench/peaks.json`` and that has the chips the cell asks for (exit 2,
   no result);
2. sets up: the data on the device from ``--seed``, the sites, one warm
   job (the compile cache lives at a fixed path in the checkout);
3. drives a closed loop of jobs for ``--seconds``, each inside a
   ``bench.job`` span, all inside one ``bench.window`` span; with
   ``--trace 1`` then the traffic's ``traced_jobs`` more under the
   profiler (a window of a few jobs: a fit runs some 10^5 device ops);
4. reads the device's peak memory, frees the program's state, and
   compares the checked jobs' answers with the plain reference
   (``bench/limits/<workload>.json`` holds each number's limit);
5. prints the compared numbers with their limits as the last lines of
   standard error, and one JSON line as the last line of standard
   output: the cell's end-to-end metrics, or with ``--trace 1`` its
   per-layer metrics (each read by ``bench/metrics/<name>.py``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    # the checkout's root, not bench/, leads the path: bench/trace.py must
    # not stand in for the standard library's ``trace``
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))


class NoDevice(RuntimeError):
    """The machine does not hold what the cell needs."""


@dataclasses.dataclass
class Context:
    """What a metric reader reads."""

    config: dict
    cell: object
    answers: list
    job_s: list
    window_s: float
    setup_s: float
    peaks: dict
    trace: object = None  # the traced window's ``trace.Summary``
    traced: list = dataclasses.field(default_factory=list)  # its answers


def load_cell(workload: str, root: pathlib.Path = ROOT):
    """(benchmark, cell, config, traffic, limits) for ``workload``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    limits = json.loads(
        (root / "bench" / "limits" / f"{workload}.json").read_text())
    return bench, cell, config, traffic, limits


def cell_metrics(bench: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics this cell reports in this kind of run."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def check_device(chips: int, peaks: dict):
    """The devices, or ``NoDevice`` where they are not what the cell needs."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"JAX finds no TPU (platform "
                       f"{devices[0].platform!r})")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise NoDevice(f"device kind {kind!r} is not in bench/peaks.json")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees "
                       f"{len(devices)}")
    return devices[:chips]


class CompileCounter:
    """Counts compilations and compile-cache loads while it is armed."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.armed and event in self.EVENTS:
            self.count += 1


def window(cell, jobs, seconds: float | None = None,
           count: int | None = None):
    """A closed loop: jobs back to back until ``seconds`` have passed, or
    ``count`` jobs have run, all inside one ``bench.window`` span.

    Returns (answers, per-job seconds, window seconds, failed jobs)."""
    import jax

    answers, job_s, failed = [], [], 0
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        while True:
            job = next(jobs)
            with jax.profiler.TraceAnnotation("bench.job"):
                t1 = time.perf_counter()
                try:
                    answers.append(cell.run_job(job))
                except Exception as e:  # a job that fails is counted
                    failed += 1
                    print(f"job failed: {type(e).__name__}: {e}",
                          file=sys.stderr)
                t2 = time.perf_counter()
            job_s.append(t2 - t1)
            if (len(job_s) >= count) if count else (t2 - t0 >= seconds):
                break
    return answers, job_s, t2 - t0, failed


def traced_window(cell, jobs, count: int):
    """``count`` more jobs under the profiler and the program's own span
    tracer: (the window's answers, failed jobs, its trace ``Summary``)."""
    import jax
    from repro.obs import trace as program_trace

    from bench import trace as trace_mod

    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        program_trace.enable(profiler=True)
        jax.profiler.start_trace(log_dir)
        try:
            answers, _, _, failed = window(cell, jobs, count=count)
        finally:
            jax.profiler.stop_trace()
            spans = program_trace.disable().spans
        summary = trace_mod.reduce(trace_mod.load(
            trace_mod.find_xplane(log_dir), {sp.name for sp in spans}))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    return answers, failed, summary


def compare(cell, answers, limits: dict) -> tuple[bool, dict]:
    """Each compared number beside its limit; ``correct`` needs all under."""
    import jax

    if not answers:
        return False, {}
    cpu = jax.devices("cpu")[0]
    parts_ref = jax.device_put(cell.parts, cpu)
    numbers = cell.compare(answers, parts_ref)
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok and numbers.get("jobs_compared", 0) > 0, checks


def run(workload: str, seed: int, seconds: float, trace: bool,
        root: pathlib.Path = ROOT) -> dict:
    bench, spec, config, traffic, limits = load_cell(workload, root)
    peaks_all = json.loads((root / "bench" / "peaks.json").read_text())
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    t_import = time.perf_counter()
    devices = check_device(spec["chips"], peaks_all)
    # a fixed path in the checkout, or JAX_COMPILATION_CACHE_DIR's
    enable_compile_cache()
    jax.config.update("jax_enable_x64", True)
    kind = importlib.import_module(f"bench.kinds.{traffic['kind']}")
    cell = kind.Cell(config, traffic, seed)
    t_devices = time.perf_counter()
    phases = cell.setup()
    setup_s = time.perf_counter() - T_START
    print(f"setup: {setup_s!r} s: imports {t_import - T_START!r}, devices "
          f"{t_devices - t_import!r}, " + ", ".join(
              f"{k} {v!r}" for k, v in phases.items()), file=sys.stderr)
    counter = CompileCounter()
    jobs = cell.jobs()
    counter.armed = True
    answers, job_s, window_s, failed = window(cell, jobs, seconds=seconds)
    attempted = len(job_s)
    traced, summary = [], None
    if trace:
        # after the measured window, so the profiler slows none of it
        traced, more_failed, summary = traced_window(
            cell, jobs, int(traffic["traced_jobs"]))
        failed += more_failed
        attempted += len(traced) + more_failed
    counter.armed = False
    stats = devices[0].memory_stats() or {}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices) if stats else None
    print(f"compilations in the window: {counter.count}", file=sys.stderr)
    cell.release()
    t_compare = time.perf_counter()
    correct, checks = compare(cell, answers + traced, limits)
    print(f"reference: {time.perf_counter() - t_compare!r} s",
          file=sys.stderr)
    correct = correct and failed == 0  # an answer that never came

    kind_name = devices[0].device_kind
    ctx = Context(config, cell, answers, job_s, window_s, setup_s,
                  peaks_all.get(kind_name, {}), summary, traced)
    metrics = {}
    for m in cell_metrics(bench, workload, trace):
        reader = importlib.import_module(f"bench.metrics.{m['name']}")
        value = reader.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind_name,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        ops = sorted(summary.op_s.items(), key=lambda kv: -kv[1])[:10]
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in summary.gaps[:10]],
        }
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
