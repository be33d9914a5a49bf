"""Autoregressive decode through the pipeline (beyond-paper feature):
token-exact vs single-device greedy decode."""
import subprocess
import sys

import importlib

import jax
import jax.numpy as jnp
import pytest

from repro.launch.mesh import make_auto_mesh
from repro.launch.serve import build_pipeline_decoder
from repro.models import transformer as T


def _ref_greedy(cfg, params, start_m, mb, steps, max_len):
    caches = T.init_caches(cfg, mb, max_len, jnp.float32)
    tok = start_m
    out = []
    for p in range(steps):
        lg, caches = T.decode_step(params, cfg, tok,
                                   jnp.full((mb,), p, jnp.int32), caches)
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
        out.append(tok[:, 0])
    return jnp.stack(out)


@pytest.mark.parametrize("arch,M", [("phi3_mini_3_8b", 2),
                                    ("mamba2_2_7b", 3),
                                    ("zamba2_2_7b", 2)])
def test_pipeline_decode_matches_greedy_single_stage(arch, M):
    cfg = importlib.import_module(f"repro.configs.{arch}").smoke_config()
    params = T.init_lm(cfg, jax.random.PRNGKey(0))
    mesh = make_auto_mesh((1,), ("stage",))
    mb, steps, max_len = 2, 4, 16
    start = jax.random.randint(jax.random.PRNGKey(1), (M, mb, 1), 0,
                               cfg.vocab)
    start_pos = jnp.zeros((M, mb), jnp.int32)
    fn, sw, caches0, head = build_pipeline_decoder(
        cfg, params, mesh, 1, M, mb, max_len, steps)
    with mesh:
        toks, _ = jax.jit(fn)(sw, caches0, start, start_pos, head)
    for m in range(M):
        ref = _ref_greedy(cfg, params, start[m], mb, steps, max_len)
        assert bool((toks[m] == ref).all()), (arch, m)


_MULTISTAGE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, importlib
from repro.launch.mesh import make_auto_mesh
from repro.launch.serve import build_pipeline_decoder
from repro.models import transformer as T

cfg = importlib.import_module("repro.configs.phi3_mini_3_8b").smoke_config()
params = T.init_lm(cfg, jax.random.PRNGKey(0))
mesh = make_auto_mesh((4,), ("stage",))
M, mb, steps, max_len = 6, 2, 5, 16
start = jax.random.randint(jax.random.PRNGKey(1), (M, mb, 1), 0, cfg.vocab)
start_pos = jnp.zeros((M, mb), jnp.int32)
fn, sw, caches0, head = build_pipeline_decoder(
    cfg, params, mesh, 4, M, mb, max_len, steps)
with mesh:
    toks, _ = jax.jit(fn)(sw, caches0, start, start_pos, head)
for m in range(M):
    caches = T.init_caches(cfg, mb, max_len, jnp.float32)
    tok = start[m]
    for p in range(steps):
        lg, caches = T.decode_step(params, cfg, tok,
                                   jnp.full((mb,), p, jnp.int32), caches)
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
        assert bool((toks[m, p] == tok[:, 0]).all()), (m, p)
print("OK")
"""


@pytest.mark.slow
def test_pipeline_decode_multistage_subprocess():
    r = subprocess.run([sys.executable, "-c", _MULTISTAGE],
                       capture_output=True, text=True, timeout=900,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"},
                       cwd="/root/repo")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
