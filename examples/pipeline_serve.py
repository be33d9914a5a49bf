"""TPU-path serving: the DEFER chain as shard_map pipeline parallelism,
with and without the int8 wire codec (the ZFP adaptation).

Runs a smoke-size model over 4 emulated devices in a fresh process:

    PYTHONPATH=src python examples/pipeline_serve.py --arch gemma3-4b
"""
import os

if "--_child" not in os.sys.argv and "XLA_FLAGS" not in os.environ:
    # re-exec with 4 emulated devices before jax initializes
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.registry import ARCHS, get_smoke
from repro.launch.mesh import make_auto_mesh
from repro.launch.serve import build_pipeline_lm, wire_bytes_per_relay
from repro.models import transformer as T


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="gemma3-4b")
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    args = ap.parse_args()

    cfg = get_smoke(args.arch)
    S = args.stages
    if jax.device_count() < S:
        raise SystemExit("need XLA_FLAGS=--xla_force_host_platform_device_count>=4")
    mesh = make_auto_mesh((S,), ("stage",))
    params = T.init_lm(cfg, jax.random.PRNGKey(0))
    B = args.microbatches * 4
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, args.seq), 0,
                                cfg.vocab)
    kw = {}
    if cfg.num_prefix_embeds and not cfg.encoder_layers:
        kw["prefix_embeds"] = jnp.zeros((B, cfg.num_prefix_embeds, cfg.d_model))
    if cfg.encoder_layers:
        kw["encoder_embeds"] = jnp.zeros((B, cfg.num_prefix_embeds, cfg.d_model))

    ref, _ = T.forward(params, cfg, tokens, **kw)
    for compress in (False, True):
        lm = build_pipeline_lm(cfg, params, mesh, S, args.microbatches,
                               compress=compress)
        with mesh:
            f = jax.jit(lambda t: lm(t, **kw))
            out = f(tokens)
            out.block_until_ready()
            t0 = time.perf_counter()
            out = f(tokens)
            out.block_until_ready()
            dt = time.perf_counter() - t0
        err = float(jnp.abs(out - ref).max() / jnp.abs(ref).max())
        wire = wire_bytes_per_relay(cfg, B // args.microbatches, args.seq,
                                    compress)
        print(f"compress={compress!s:5s} wall={dt*1e3:7.1f} ms "
              f"relay={wire/1e3:8.1f} kB/hop rel_err={err:.4f}")
    print(f"\n{args.arch}: {S}-stage pipeline == single-device forward "
          f"(uncompressed err must be ~0)")


if __name__ == "__main__":
    main()
