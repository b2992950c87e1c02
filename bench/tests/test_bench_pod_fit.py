"""The pod cell: its files, its readers of the cross-chip wire, a run of
the cell on four CPU devices, and a program without pods refused at once.
"""
import json
import os
import shutil
import subprocess
import sys
import textwrap
import types

import pytest

from bench import trace, wire
from conftest import ROOT

WIRE_HLO = textwrap.dedent("""\
    HloModule jit_fit_scan_block

    ENTRY %main {
      %fusion.7 = u32[2,3,2,8,128]{4,3,2,1,0} fusion(u32[3,2,8,128]{3,2,1,0} %p), kind=kLoop, metadata={op_name="jit(fit_scan_block)/while/body/aggregate/pod_wire/and"}
      %all-reduce.8 = u32[2,3,2,8,128]{4,3,2,1,0} all-reduce(u32[2,3,2,8,128]{4,3,2,1,0} %fusion.7), replica_groups={{0,1,2,3}}, metadata={op_name="jit(fit_scan_block)/while/body/aggregate/pod_wire/psum"}
      %fusion.9 = u32[3,2,8,128]{3,2,1,0} fusion(u32[3,2,8,128]{3,2,1,0} %q), kind=kLoop, metadata={op_name="jit(fit_scan_block)/while/body/aggregate/reduce_sum"}
      %fusion.10 = f64[8]{0} fusion(f64[8]{0} %b), kind=kLoop, metadata={op_name="jit(fit_scan_block)/while/body/newton_solve/add"}
    }
    """)


def _summary(devices=4):
    ops = []
    for _ in range(devices):
        ops.append([
            ("%fusion.7 = u32[2,3,2,8,128]{4,3,2,1,0} fusion(u32[3,2,8", 0,
             100),
            ("%all-reduce.8 = u32[2,3,2,8,128]{4,3,2,1,0} all-reduce(u32", 100,
             400),
            ("%fusion.9 = u32[3,2,8,128]{3,2,1,0} fusion(u32[3,2", 400, 500),
            ("%fusion.10 = f64[8]{0} fusion(f64[8]{0} %b)", 500, 900),
        ])
    return trace.reduce(trace.Trace(ops, [("bench.window", 0, 1000)]))


def _ctx(answers, summary):
    cell = types.SimpleNamespace(
        kernel_work=lambda answers: {"pod_wire": 2e5 * sum(
            a.rounds for a in answers)})
    return types.SimpleNamespace(trace=summary, traced=answers, cell=cell)


def _answer(rounds, wire_bytes):
    return types.SimpleNamespace(rounds=rounds, wire_bytes=wire_bytes)


def test_wire_seconds_read_the_pod_wire_scope_per_device():
    # fusion.7 and the all-reduce, 400 ns a device, averaged over four
    assert wire.wire_seconds(_summary(), [WIRE_HLO]) == pytest.approx(4e-7)
    # a program without the scope: nothing to read
    plain = WIRE_HLO.replace("/pod_wire", "")
    assert wire.wire_seconds(_summary(), [plain]) is None


def test_the_wire_readers(monkeypatch):
    import jax

    from bench.metrics import (pod_wire_kb_per_round, pod_wire_ms_per_round,
                               pod_wire_roofline)

    monkeypatch.setattr(wire, "live_hlo_texts", lambda: [WIRE_HLO])
    kind = types.SimpleNamespace(device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [kind])
    ctx = _ctx([_answer(3, 3 * 835584.0), _answer(5, 5 * 835584.0)],
               _summary())
    assert pod_wire_ms_per_round.read(ctx) == pytest.approx(4e-4 / 8)
    # 8 rounds of 2e5 bytes at 200 GB/s, over 400 ns
    assert pod_wire_roofline.read(ctx) == pytest.approx(
        100.0 * 8 * 2e5 / 200e9 / 4e-7)
    assert pod_wire_kb_per_round.read(ctx) == pytest.approx(835.584)
    # the parent: no counter, no scope
    ctx = _ctx([_answer(3, None)], _summary())
    assert pod_wire_kb_per_round.read(ctx) is None
    monkeypatch.setattr(wire, "live_hlo_texts",
                        lambda: [WIRE_HLO.replace("/pod_wire", "")])
    assert pod_wire_ms_per_round.read(ctx) is None
    assert pod_wire_roofline.read(ctx) is None


def test_ring_bytes_count_field_words_of_the_protected_tree():
    # 2-of-3 over two residues, d = 128 with protect both: 16,514 words
    # a share and residue, 3/2 of them across a ring of four
    got = wire.ring_bytes(128, "both", 2, 3, 4)
    assert got == 1.5 * 4 * 3 * 2 * (128 * 128 + 128 + 2)


def test_the_pod_config_keeps_the_source_at_full_rows():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (cell,) = [w for w in bench["workloads"] if w["name"] == "d128-pods4"]
    (conf,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    one_chip = json.loads(
        (ROOT / "bench" / "configs" / "consortium-d128.json").read_text())
    assert cell["chips"] == config["pods"] == 4
    assert config["rows"] == one_chip["source_rows"] == 200859
    assert config["reduced"] == conf["reduced"] == []
    for key in ("sites", "split", "split_exponent", "features", "shamir",
                "protect", "summaries", "precision", "tol", "max_rounds",
                "generator"):
        assert config[key] == one_chip[key], key
    limits = json.loads(
        (ROOT / "bench" / "limits" / "d128-pods4.json").read_text())
    assert limits == json.loads(
        (ROOT / "bench" / "limits" / "d128-fit.json").read_text())


def test_a_program_without_pods_is_refused_before_any_data(monkeypatch):
    from bench.kinds import pod_fit, single_fit

    class OnePod:
        def __init__(self, institutions, lam=1.0):
            pass

    monkeypatch.setattr(single_fit, "_program", lambda: types.SimpleNamespace(
        StudyCoordinator=OnePod))
    config = json.loads(
        (ROOT / "bench" / "configs" / "eicu-pods4.json").read_text())
    cell = pod_fit.Cell(config, {"lambdas": [1.0]}, 1)
    with pytest.raises(RuntimeError, match="takes no 'pods'"):
        cell.setup()
    assert cell.parts is None


_RUN = textwrap.dedent("""
    import json, pathlib, sys
    import jax
    sys.path[:0] = [%(root)r, %(src)r]
    from bench import run
    run.check_device = lambda chips, peaks: jax.devices()[:chips]
    import repro.launch.compile_cache as cc
    cc.enable_compile_cache = lambda: ""
    res = run.run("d128-pods4", 2**31 + 7, 1.0, False,
                  root=pathlib.Path(%(tiny)r))
    print(json.dumps(res))
""")


def test_the_cell_runs_on_four_devices(tmp_path):
    """The cell end to end on four CPU devices at a test's size: the
    sites on their pods, correct against the pooled reference."""
    tiny = tmp_path / "root"
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tiny / "bench").mkdir(parents=True)
    (tiny / "BENCHMARK.json").write_text(json.dumps(bench))
    for sub in ("configs", "traffic", "limits"):
        shutil.copytree(ROOT / "bench" / sub, tiny / "bench" / sub)
    p = tiny / "bench" / "configs" / "eicu-pods4.json"
    config = json.loads(p.read_text())
    config.update(rows=2400, sites=8)
    p.write_text(json.dumps(config))
    (tiny / "bench" / "peaks.json").write_text(
        (ROOT / "bench" / "peaks.json").read_text())
    script = tmp_path / "run_pods.py"
    script.write_text(_RUN % {"root": str(ROOT), "src": str(ROOT / "src"),
                              "tiny": str(tiny)})
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert res["device"]["count"] == 4
    assert res["checks"]["rounds_gap"]["value"] == 0
    assert set(res["metrics"]) == {"job_ms", "setup_s"}
