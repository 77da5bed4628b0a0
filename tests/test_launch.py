"""Launcher integration: dry-run cell in subprocess (512 devices), train
driver with failure injection, serve driver."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _run(args, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, env=env, timeout=timeout, cwd=REPO)


@pytest.mark.slow
def test_dryrun_single_cell_subprocess(tmp_path):
    proc = _run(["-m", "repro.launch.dryrun", "--arch", "qwen3_1_7b",
                 "--shape", "decode_32k", "--mesh", "single",
                 "--out-dir", str(tmp_path), "--force"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    cell = json.loads((tmp_path / "qwen3_1_7b__decode_32k__single.json").read_text())
    assert "error" not in cell, cell.get("error")
    assert cell["n_devices"] == 256
    assert cell["hlo_flops_per_device"] > 0
    assert cell["roofline"]["dominant"] in ("compute_s", "memory_s", "collective_s")
    assert cell["memory"]["temp_bytes"] is not None


@pytest.mark.slow
def test_dryrun_multipod_cell_subprocess(tmp_path):
    proc = _run(["-m", "repro.launch.dryrun", "--arch", "mamba2_780m",
                 "--shape", "long_500k", "--mesh", "multi",
                 "--out-dir", str(tmp_path), "--force"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    cell = json.loads((tmp_path / "mamba2_780m__long_500k__multi.json").read_text())
    assert "error" not in cell, cell.get("error")
    assert cell["n_devices"] == 512  # the pod axis sharded


@pytest.mark.slow
def test_train_launcher_failure_injection(tmp_path):
    proc = _run(["-m", "repro.launch.train", "--arch", "qwen3_1_7b",
                 "--steps", "25", "--ckpt-dir", str(tmp_path / "ck"),
                 "--ckpt-every", "10", "--fail-at", "12,1,3",
                 "--peak-lr", "5e-3", "--seq-len", "64", "--batch", "4"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "reconstructed from parity" in proc.stdout
    assert "done: final loss" in proc.stdout


@pytest.mark.slow
def test_serve_launcher():
    proc = _run(["-m", "repro.launch.serve", "--arch", "hymba_1_5b",
                 "--batch", "2", "--prompt-len", "8", "--gen-len", "8"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "generated 8 tokens/seq" in proc.stdout


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself and the
    helper sets no path of its own."""
    import jax

    from repro.compile_cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_and_ignored(monkeypatch):
    """Unset, the cache goes to one fixed, git-ignored path in the
    checkout."""
    import jax

    from repro.compile_cache import enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
        assert enable_compile_cache() == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert Path(path) == REPO / ".jax_cache"
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
