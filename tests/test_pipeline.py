"""TPU pipeline path: schedule correctness, stage stacking, wire quant,
multi-device equivalence (subprocess with 4 fake devices)."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pipeline import (PipelineConfig, make_pipeline,
                                 make_stage_unit_fn, pipeline_apply,
                                 stack_stages)
from repro.launch.mesh import make_auto_mesh


def test_stack_stages_padding_and_mask():
    w = jnp.arange(7 * 3).reshape(7, 3).astype(jnp.float32)
    stacked, valid = stack_stages(w, 7, 4)
    assert stacked.shape == (4, 2, 3)
    assert valid.tolist() == [[True, True], [True, True], [True, True],
                              [True, False]]
    np.testing.assert_array_equal(np.asarray(stacked[3, 1]), np.zeros(3))


def test_stack_stages_exact_division():
    w = jnp.ones((8, 2))
    stacked, valid = stack_stages(w, 8, 4)
    assert stacked.shape == (4, 2, 2) and bool(valid.all())


def test_single_stage_pipeline_equals_sequential():
    """S=1 runs on one real device; schedule reduces to a plain loop."""
    d = 16
    w = jax.random.normal(jax.random.PRNGKey(0), (3, d, d)) * 0.1

    def apply_unit(up, x):
        return x + jnp.tanh(x @ up)

    mesh = make_auto_mesh((1,), ("stage",))
    stacked, valid = stack_stages(w, 3, 1)
    fn = make_pipeline(mesh, PipelineConfig(1, 4),
                       make_stage_unit_fn(apply_unit))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 2, 8, d))
    with mesh:
        y = jax.jit(fn)((stacked, valid), x)
    ref = x
    for i in range(3):
        ref = apply_unit(w[i], ref)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)


def test_wire_quant_roundtrip_in_pipeline_codec():
    from repro.core.pipeline import _wire_decode, _wire_encode
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 96))
    q, sc = _wire_encode(x, "jnp")
    assert q.dtype == jnp.int8
    back = _wire_decode(q, sc, x.shape, x.dtype, "jnp")
    assert back.shape == x.shape
    err = jnp.abs(back - x).max()
    assert err <= jnp.abs(x).max() / 127.0 + 1e-6


_MULTIDEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np, importlib
from repro.launch.mesh import make_auto_mesh
from repro.launch.serve import build_pipeline_lm
from repro.models import transformer as T

failures = []
for a in ["phi3_mini_3_8b", "zamba2_2_7b", "seamless_m4t_large_v2"]:
    cfg = importlib.import_module(f"repro.configs.{a}").smoke_config()
    params = T.init_lm(cfg, jax.random.PRNGKey(0))
    mesh = make_auto_mesh((4,), ("stage",))
    B, S, M = 8, 16, 4
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    kw = {}
    if cfg.encoder_layers:
        kw["encoder_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2), (B, cfg.num_prefix_embeds, cfg.d_model)) * 0.1
    ref, _ = T.forward(params, cfg, tokens, **kw)
    lm = build_pipeline_lm(cfg, params, mesh, 4, M, compress=False)
    with mesh:
        out = jax.jit(lambda t: lm(t, **kw))(tokens)
    err = float(jnp.abs(out - ref).max())
    if err > 1e-4:
        failures.append((a, err))
    lmc = build_pipeline_lm(cfg, params, mesh, 4, M, compress=True)
    with mesh:
        outc = jax.jit(lambda t: lmc(t, **kw))(tokens)
    rel = float(jnp.abs(outc - ref).max() / jnp.abs(ref).max())
    if rel > 0.15:
        failures.append((a + "+compress", rel))
assert not failures, failures
print("OK")
"""


@pytest.mark.slow
def test_pipeline_lm_multidevice_subprocess():
    """4-stage pipeline == single-device forward, for 3 families, on 4
    fake devices (own process so the 1-device test env is untouched)."""
    r = subprocess.run([sys.executable, "-c", _MULTIDEV_SCRIPT],
                       capture_output=True, text=True, timeout=900,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"},
                       cwd="/root/repo")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
