"""Entry-point plumbing: the compile-cache helper and the chip check's
device gate."""
import os
import subprocess
import sys

import jax
import pytest

from repro import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    cc.reset_cache()


def test_compile_cache_leaves_env_setting_alone(monkeypatch, tmp_path,
                                                restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(os.path.realpath(ROOT), ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_written_where_env_says(tmp_path):
    """A process that calls the helper with the variable set writes its
    compiled programs there."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=os.path.join(ROOT, "src"))
    code = ("from repro.compile_cache import enable_compile_cache\n"
            "enable_compile_cache()\n"
            "import jax\n"
            "jax.jit(lambda x: x * 2)(1.0).block_until_ready()\n")
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)
    assert os.listdir(tmp_path)


def test_chip_smoke_refuses_the_cpu():
    """Without a TPU the chip check exits non-zero, says why, and prints
    no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "no TPU found" in r.stdout
    assert '"ok"' not in r.stdout
