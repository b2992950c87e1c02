"""The harness: found by name, refuses what is not a TPU, prints one line."""
import importlib
import json
import re

import pytest

from bench import run
from conftest import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_files_are_found_by_name(workload):
    bench, cell, config, traffic, limits = run.load_cell(workload)
    kind = importlib.import_module(f"bench.kinds.{traffic['kind']}")
    assert hasattr(kind, "Cell")
    assert config["name"] == cell["config"]
    assert set(limits) and all(v >= 0 for v in limits.values())
    assert run.cell_metrics(bench, workload, False)
    assert run.cell_metrics(bench, workload, True)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["end_to_end"]
                                    + BENCH["per_layer"]])
def test_every_metric_has_a_reader(metric):
    assert NAME.match(metric)
    reader = importlib.import_module(f"bench.metrics.{metric}")
    assert callable(reader.read)


def test_benchmark_json_names_and_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in BENCH["configs"]] + WORKLOADS + [
        m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", WORKLOADS)) <= set(WORKLOADS)


def test_a_cpu_run_exits_nonzero_with_no_result(capsys):
    rc = run.main(["--workload", WORKLOADS[0], "--seed", "1",
                   "--seconds", "1"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert out == ""
    assert "no TPU" in err


class _Device:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


def test_unknown_device_kind_and_too_few_chips_are_refused(monkeypatch):
    import jax

    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    monkeypatch.setattr(jax, "devices", lambda *a: [_Device("TPU v9")])
    with pytest.raises(run.NoDevice, match="not in bench/peaks.json"):
        run.check_device(1, peaks)
    monkeypatch.setattr(jax, "devices", lambda *a: [_Device("TPU v5 lite")])
    assert len(run.check_device(1, peaks)) == 1
    with pytest.raises(run.NoDevice, match="needs 4 chips"):
        run.check_device(4, peaks)


@pytest.mark.parametrize("trace", [False, True])
def test_the_result_line(tiny_root, trace, monkeypatch, no_chip):
    if trace:
        # the CPU has no TPU plane: hand the reduction a recorded trace
        from bench import trace as trace_mod

        recorded = trace_mod.Trace.from_json(
            (ROOT / "bench" / "tests" / "data" / "small_trace.json")
            .read_text())
        monkeypatch.setattr(trace_mod, "load", lambda path, names=(): recorded)
        # the readers look the peaks up by device kind: give the CPU some
        peaks = json.loads((tiny_root / "bench" / "peaks.json").read_text())
        peaks["cpu"] = peaks["TPU v5 lite"]
        (tiny_root / "bench" / "peaks.json").write_text(json.dumps(peaks))
    res = run.run("d128-fit", 2**31 + 12345, 1.0, trace, root=tiny_root)
    assert list(res)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(
        res)
    assert res["correct"] is True
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    want = {m["name"] for m in run.cell_metrics(BENCH, "d128-fit", trace)}
    assert set(res["metrics"]) <= want
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}
    if trace:
        assert {"busy_s", "window_s"} <= set(res["device"])
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(res["metrics"]) == want
    json.dumps(res)
