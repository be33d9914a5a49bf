"""TPU-path wire benchmark: relay bytes + quantization error of the DEFER
pipeline's compressed relay (the ZFP adaptation), per assigned arch.

This is the TPU analogue of Table I's "Data" rows: raw bf16 relay vs int8
block-quant relay, bytes per microbatch hop and end-to-end logit error on
the smoke configs.  Also measures the same kernel through the serving
runtime's ``WireCodec("q8")`` wire path (the q8 serializer the staged
relay threads ship between nodes): payload ratio and worst-case error vs
the codec's stated bound on a full-width activation slab."""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.compile_cache import enable_compile_cache
from repro.configs.registry import ARCHS
from repro.kernels import ops as kops
from repro.launch.mesh import make_auto_mesh
from repro.launch.serve import build_pipeline_lm
from repro.models import transformer as T
from repro.runtime.wire import WireCodec


def run(archs=("phi3-mini-3.8b", "gemma3-4b", "dbrx-132b", "mamba2-2.7b"),
        stages: int = 2) -> list[dict]:
    rows = []
    for arch in archs:
        from repro.configs.registry import get_smoke, get_config
        cfg = get_smoke(arch)
        full = get_config(arch)
        params = T.init_lm(cfg, jax.random.PRNGKey(0))
        mesh = make_auto_mesh((1,), ("stage",))
        B, S, M = 4, 32, 2
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                    cfg.vocab)
        kw = {}
        if cfg.num_prefix_embeds and not cfg.encoder_layers:
            kw["prefix_embeds"] = jnp.zeros((B, cfg.num_prefix_embeds,
                                             cfg.d_model))
        if cfg.encoder_layers:
            kw["encoder_embeds"] = jnp.zeros((B, cfg.num_prefix_embeds,
                                              cfg.d_model))
        # sanity: the (single-stage) pipeline must reproduce forward exactly
        ref, _ = T.forward(params, cfg, tokens, **kw)
        lm = build_pipeline_lm(cfg, params, mesh, 1, M, compress=False)
        with mesh:
            out = jax.jit(lambda t: lm(t, **kw))(tokens)
        assert float(jnp.abs(out - ref).max()) < 1e-4
        # full-size wire bytes for one relay hop (mb=8, seq=4096); the
        # multi-stage compressed-relay error is asserted in
        # tests/test_pipeline.py (needs >=2 devices)
        raw, wire = kops.quant_bytes((8 * 4096, full.d_model), jnp.bfloat16)
        # the serving runtime's q8 wire path over the same kernel: one
        # activation slab (256 rows x d_model) through WireCodec("q8")
        q8 = WireCodec("q8", "none")
        slab = np.random.default_rng(0).normal(
            size=(256, full.d_model)).astype(np.float32)
        q8_blob = q8.encode_array(slab)
        q8_err = float(np.abs(q8.decode_array(q8_blob) - slab).max())
        q8_bound = q8.error_bound(float(np.abs(slab).max()))
        rows.append({
            "arch": arch, "relay_raw_mb": raw / 1e6,
            "relay_quant_mb": wire / 1e6, "ratio": wire / raw,
            "q8_wire_ratio": len(q8_blob) / slab.nbytes,
            "q8_max_err": q8_err, "q8_err_bound": q8_bound,
            "q8_within_bound": q8_err <= q8_bound,
        })
    return rows


def main() -> None:
    emit("pipeline_wire", run())


if __name__ == "__main__":
    enable_compile_cache()
    main()
