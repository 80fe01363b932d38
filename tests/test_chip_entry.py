"""Entry-point plumbing for chip runs, checked on the CPU: where the compile
cache goes, and that ``chip_smoke.py`` refuses to run without a TPU."""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import cache

ROOT = Path(__file__).resolve().parent.parent


def test_compile_cache_follows_env_else_fixed_in_checkout(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert cache.place_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        first = cache.place_compile_cache()
        second = cache.place_compile_cache()
        assert first == second == ROOT / ".jax_cache"
        assert jax.config.jax_compilation_cache_dir == str(first)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_chip_smoke_refuses_a_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    for line in proc.stdout.splitlines():
        assert not line.startswith("{"), f"printed a result: {line}"
