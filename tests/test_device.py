"""repro.device: the platform decisions and the compile-cache placement,
and the entry points that must refuse to run off the TPU."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro import device

ROOT = Path(__file__).resolve().parents[1]


def test_platform_helpers_on_cpu():
    assert jax.default_backend() == "cpu"
    assert device.interpret_mode()
    with pytest.raises(RuntimeError, match="needs a TPU"):
        device.require_tpu("this")


def test_compile_cache_env_is_left_to_jax(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(device.CACHE_ENV, str(tmp_path))
    assert device.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set in code


def test_compile_cache_default_is_fixed_and_ignored(monkeypatch):
    monkeypatch.delenv(device.CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert device.enable_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()


def _run_on_cpu(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    return subprocess.run(
        [sys.executable, *argv], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_chip_smoke_exits_before_any_phase_off_tpu():
    r = _run_on_cpu("chip_smoke.py")
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert r.stdout == ""  # no phase ran, no result line


def test_train_dcgan_auto_impl_refuses_off_tpu():
    r = _run_on_cpu("examples/train_dcgan.py", "--steps", "1")
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
