"""Integration tests: train.py / serve.py drivers end-to-end on CPU."""
import json
import os

import numpy as np
import pytest

from repro.launch import serve as serve_mod
from repro.launch import train as train_mod


def test_logreg_driver_converges(tmp_path):
    out = tmp_path / "m.json"
    train_mod.main([
        "--arch", "logreg_paper", "--study", "parkinsons.total",
        "--scale", "0.05", "--out", str(out),
    ])
    m = json.loads(out.read_text())
    assert m["converged"] and m["r2_vs_gold"] > 0.999999
    assert m["iterations"] <= 10


def test_compile_cache_follows_env_or_a_fixed_checkout_path(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX; otherwise the
    cache goes to one fixed, gitignored directory in the checkout."""
    import jax

    from repro.launch import compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.enable_compile_cache() == "/elsewhere"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.enable_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(repo, ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]
    assert compile_cache.enable_compile_cache() == path  # no moving target


def test_lm_driver_secure_agg_loss_decreases(tmp_path):
    out = tmp_path / "m.json"
    train_mod.main([
        "--arch", "rwkv6_3b", "--smoke", "--steps", "8",
        "--batch", "4", "--seq-len", "32", "--lr", "1e-2",
        "--secure-agg", "shamir", "--institutions", "2",
        "--out", str(out),
    ])
    m = json.loads(out.read_text())
    assert m["loss_last"] < m["loss_first"]


def test_lm_driver_checkpoint_resume(tmp_path):
    ck = tmp_path / "ck"
    args = [
        "--arch", "deepseek_7b", "--smoke", "--batch", "4",
        "--seq-len", "32", "--checkpoint-dir", str(ck),
        "--checkpoint-every", "3",
    ]
    train_mod.main(args + ["--steps", "6"])
    saved = sorted(os.listdir(ck))
    assert any("0000000006" in s for s in saved)
    out = tmp_path / "m.json"
    train_mod.main(args + ["--steps", "9", "--resume", "--out", str(out)])
    m = json.loads(out.read_text())
    assert m["steps"] == 3  # resumed at 6, ran to 9


def test_lm_driver_failure_injection():
    # institution 3 dies at step 2; loop proceeds with survivors
    train_mod.main([
        "--arch", "qwen2_5_32b", "--smoke", "--steps", "4",
        "--batch", "4", "--seq-len", "32",
        "--institutions", "4", "--fail-at", "2",
    ])


def test_serve_driver_batched_decode():
    rep = serve_mod.main([
        "--arch", "h2o_danube3_4b", "--requests", "5", "--batch", "2",
        "--prompt-len", "16", "--new-tokens", "4",
    ])
    assert rep["tokens_generated"] == 5 * 4
    assert len(rep["sample_output"]) == 4
