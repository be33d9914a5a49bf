"""§Perf variants: numerics of the optimized paths == the baselines."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig, ModelConfig
from repro.models import moe as M
from repro.models import transformer as T

KEY = jax.random.PRNGKey(0)


def test_vocab_padding_preserves_semantics():
    """Padded-vocab logits == unpadded logits on the real ids; pad ids are
    -inf (HC1)."""
    base = ModelConfig(name="t", family="dense", num_layers=2, d_model=128,
                       num_heads=4, kv_heads=2, d_ff=256, vocab=100,
                       remat=False)
    padded = dataclasses.replace(base, vocab_pad_multiple=64)
    assert padded.padded_vocab == 128
    p0 = T.init_lm(base, KEY)
    p1 = T.init_lm(padded, KEY)
    # same init stream: embedding rows 0..99 must agree
    np.testing.assert_array_equal(
        np.asarray(p0["embed"]["table"][:100]),
        np.asarray(p1["embed"]["table"][:100]))
    tokens = jax.random.randint(KEY, (2, 8), 0, 100)
    l0, _ = T.forward(p0, base, tokens)
    l1, _ = T.forward(p1, padded, tokens)
    np.testing.assert_allclose(np.asarray(l0), np.asarray(l1[..., :100]),
                               atol=1e-5)
    assert bool((l1[..., 100:] < -1e29).all())
    # loss identical (softmax unaffected by -inf pads)
    batch = {"tokens": tokens, "labels": tokens}
    loss0, _ = T.loss_fn(p0, base, batch)
    loss1, _ = T.loss_fn(p1, padded, batch)
    np.testing.assert_allclose(float(loss0), float(loss1), rtol=1e-6)


def test_moe_sharded_dispatch_matches_global():
    """HC2 iter-1 path: per-shard capacity dispatch == global dispatch when
    capacity is generous."""
    spec_g = M.MoESpec(64, 128, True, MoEConfig(4, 2, capacity_factor=8.0))
    spec_s = M.MoESpec(64, 128, True,
                       MoEConfig(4, 2, capacity_factor=8.0, token_shards=4))
    p = M.init_moe(KEY, spec_g, jnp.float32)
    x = jax.random.normal(KEY, (4, 16, 64)) * 0.3
    og, aux_g = M.moe_block(p, spec_g, x)
    os_, aux_s = M.moe_block(p, spec_s, x)
    np.testing.assert_allclose(np.asarray(og), np.asarray(os_), atol=1e-6)
    np.testing.assert_allclose(float(aux_g), float(aux_s), rtol=1e-5)


def test_moe_sharded_dispatch_drops_locally():
    """With tight capacity, sharded dispatch drops per-shard (never crashes,
    stays finite)."""
    spec = M.MoESpec(32, 64, True,
                     MoEConfig(4, 2, capacity_factor=0.5, token_shards=2))
    p = M.init_moe(KEY, spec, jnp.float32)
    x = jax.random.normal(KEY, (2, 16, 32))
    y, aux = M.moe_block(p, spec, x)
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ["phi3_mini_3_8b", "gemma3_4b",
                                  "zamba2_2_7b"])
def test_int8_kv_cache_bounded_error(arch):
    """HC5: prefill+decode with int8 KV caches stays within quantization
    tolerance of the bf16-cache path (per-row fixed-rate, like ZFP)."""
    import importlib
    cfg = importlib.import_module(f"repro.configs.{arch}").smoke_config()
    cfgq = dataclasses.replace(cfg, kv_cache_quant=True)
    params = T.init_lm(cfg, KEY)
    B, S = 2, 16
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab)
    lp0, c0 = T.prefill(params, cfg, tokens, max_len=24)
    lpq, cq = T.prefill(params, cfgq, tokens, max_len=24)
    pos0 = cq["units"]["pos0"]
    attn_cache = pos0 if "k" in pos0 else cq["units"]["shared"]
    assert attn_cache["k"].dtype == jnp.int8
    np.testing.assert_allclose(np.asarray(lp0), np.asarray(lpq),
                               atol=0.05 * float(jnp.abs(lp0).max()))
    nt = jnp.argmax(lp0, -1).astype(jnp.int32)
    pos = jnp.full((B,), S, jnp.int32)
    lg0, _ = T.decode_step(params, cfg, nt, pos, c0)
    lgq, _ = T.decode_step(params, cfgq, nt, pos, cq)
    rel = float(jnp.abs(lg0 - lgq).max() / jnp.abs(lg0).max())
    assert rel < 0.05, rel


@pytest.mark.slow
def test_ep_pipeline_subprocess():
    """PP x EP == single-device forward (dbrx smoke, 2 stage x 2 expert)."""
    import subprocess
    import sys
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, importlib, dataclasses
from repro.core.pipeline import stack_stages
from repro.core.pipeline_ep import build_ep_pipeline
from repro.launch.mesh import make_auto_mesh
from repro.models import transformer as T
from repro.models import layers as L

cfg = importlib.import_module("repro.configs.dbrx_132b").smoke_config()
cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                       capacity_factor=8.0))
params = T.init_lm(cfg, jax.random.PRNGKey(0))
B, S, M = 4, 16, 2
tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
ref, _ = T.forward(params, cfg, tokens)
mesh = make_auto_mesh((1, 2, 2), ("data", "expert", "stage"))
n_units = cfg.num_layers // cfg.unit_layers
factory = build_ep_pipeline(cfg, mesh, num_stages=2, num_microbatches=M)
def step(params, tokens):
    x = L.embed(params["embed"], tokens)
    stacked, valid = stack_stages(params["units"], n_units, 2)
    fn = factory(stacked, valid)
    y = fn((stacked, valid), x.reshape(M, B//M, S, -1)).reshape(B, S, -1)
    y = L.rmsnorm(params["final_ln"], y, cfg.norm_eps)
    return T._mask_pad_vocab(cfg, L.linear(params["unembed"], y))
with mesh:
    out = jax.jit(step)(params, tokens)
rel = float(jnp.abs(out - ref).max() / jnp.abs(ref).max())
assert rel < 1e-4, rel
print("OK")
"""
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=900,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"},
                       cwd="/root/repo")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
