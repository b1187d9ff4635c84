"""Chip entry points that need no chip: `chip_smoke.py` refusing to run
off the chip, and the compile-cache placement rule every entry point
follows (repro.launch.compile_cache)."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _smoke(cwd, script, **env):
    e = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=e,
                          capture_output=True, text=True, timeout=300)


def _assert_refused(p):
    assert p.returncode != 0, p.stdout
    assert '"ok": true' not in p.stdout


def test_chip_smoke_refuses_without_tpu():
    p = _smoke(ROOT, ROOT / "chip_smoke.py")
    _assert_refused(p)
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("mode", ["interpret", "ref"])
def test_chip_smoke_refuses_forced_kernel_mode(mode):
    p = _smoke(ROOT, ROOT / "chip_smoke.py", REPRO_KERNEL_MODE=mode)
    _assert_refused(p)
    assert "REPRO_KERNEL_MODE" in p.stderr


def test_chip_smoke_refuses_outside_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    p = _smoke(tmp_path, tmp_path / "chip_smoke.py")
    _assert_refused(p)


def test_compile_cache_dir_follows_env(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself: nothing is set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path   # no pid/time
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
