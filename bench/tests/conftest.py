import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


import json  # noqa: E402
import shutil  # noqa: E402

import pytest  # noqa: E402

# each configuration cut to a size a test holds; widths stay as they are
TINY = {"consortium-d128": {"rows": 4000, "sites": 4},
        "paper-synthetic": {"rows": 6000}}

# cells built and tested here, not yet in BENCHMARK.json: their runs on
# the chip are not proven (PERF.md, Open questions)
PENDING = [{"name": "d128-cvpath", "config": "consortium-d128",
            "traffic": "cv_path", "chips": 1,
            "why": "closed loop of secure 5-fold CV lambda paths with "
                   "refit, one analyst"}]


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout's benchmark files with every configuration shrunk."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] += PENDING
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for sub in ("configs", "traffic", "limits"):
        shutil.copytree(ROOT / "bench" / sub, tmp_path / "bench" / sub)
    shutil.copy(ROOT / "bench" / "peaks.json", tmp_path / "bench")
    for name, cut in TINY.items():
        p = tmp_path / "bench" / "configs" / f"{name}.json"
        config = json.loads(p.read_text())
        config.update(cut)
        p.write_text(json.dumps(config))
    return tmp_path


@pytest.fixture
def no_chip(monkeypatch):
    """Let a run go past its look for a chip: the CPU's devices stand in,
    and no compile cache is written."""
    import jax
    import repro.launch.compile_cache as compile_cache

    from bench import run

    monkeypatch.setattr(run, "check_device",
                        lambda chips, peaks: jax.devices()[:chips])
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
