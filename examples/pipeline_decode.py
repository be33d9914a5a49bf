"""Beyond-paper: autoregressive generation THROUGH the DEFER pipeline.

The sampled token ppermutes from the last stage straight back to stage 0 on
the same ring that relays hidden states — no dispatcher round-trip.  With
M >= S microbatches in flight every stage is busy every tick (the paper's
FIFO law applied to decode).  Token-exact vs single-device greedy decode.

    PYTHONPATH=src python examples/pipeline_decode.py --arch phi3-mini-3.8b
"""
import os

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.registry import ARCHS, get_smoke
from repro.launch.mesh import make_auto_mesh
from repro.launch.serve import build_pipeline_decoder
from repro.models import transformer as T


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="phi3-mini-3.8b")
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--microbatches", type=int, default=6)
    ap.add_argument("--mb", type=int, default=2)
    ap.add_argument("--steps", type=int, default=8)
    args = ap.parse_args()

    cfg = get_smoke(args.arch)
    params = T.init_lm(cfg, jax.random.PRNGKey(0))
    mesh = make_auto_mesh((args.stages,), ("stage",))
    M, mb, steps = args.microbatches, args.mb, args.steps
    start = jax.random.randint(jax.random.PRNGKey(1), (M, mb, 1), 0,
                               cfg.vocab)
    start_pos = jnp.zeros((M, mb), jnp.int32)
    fn, sw, caches0, head = build_pipeline_decoder(
        cfg, params, mesh, args.stages, M, mb, steps + 8, steps)
    with mesh:
        jfn = jax.jit(fn)
        toks, _ = jfn(sw, caches0, start, start_pos, head)
        toks.block_until_ready()
        t0 = time.perf_counter()
        toks, _ = jfn(sw, caches0, start, start_pos, head)
        toks.block_until_ready()
        dt = time.perf_counter() - t0

    # verify against single-device greedy
    mismatches = 0
    for m in range(M):
        caches = T.init_caches(cfg, mb, steps + 8, jnp.float32)
        tok = start[m]
        for p in range(steps):
            lg, caches = T.decode_step(params, cfg, tok,
                                       jnp.full((mb,), p, jnp.int32), caches)
            tok = jnp.argmax(lg, -1).astype(jnp.int32)
            mismatches += int((toks[m, p] != tok[:, 0]).sum())

    n_tok = M * mb * steps
    ticks = M * steps + args.stages - 1
    print(f"{args.arch}: generated {n_tok} tokens through a "
          f"{args.stages}-stage ring in {ticks} ticks ({dt*1e3:.0f} ms)")
    print(f"token-exact vs single-device greedy: "
          f"{mismatches == 0} ({mismatches} mismatches)")
    print(f"pipeline utilisation: {M * steps / ticks:.1%} "
          f"(bubble only at fill/drain)")


if __name__ == "__main__":
    main()
