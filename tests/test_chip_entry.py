"""The chip entry points off the chip: the persistent compile cache's
location, and ``chip_smoke.py`` refusing to run without a TPU v5e."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_cache_defaults_to_ignored_dir_in_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_cache_env_var_is_left_to_jax(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


@pytest.mark.parametrize("args", [[], ["--four-chips"]])
def test_chip_smoke_refuses_without_tpu(args, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py"), *args],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    for line in r.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
