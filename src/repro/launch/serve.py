"""Serving launcher: the DEFER pipeline as a first-class deployment path.

The dispatcher role (paper Algorithm 1) maps to this module: plan the
partition (units -> stages), shard the stacked stage weights over the
"stage" mesh axis, stream microbatches through the ppermute chain, collect
FIFO results.  The wire codec (int8 block quantization, the ZFP adaptation)
is a flag, exactly like the paper's codec configurations.

    python -m repro.launch.serve --arch phi3-mini-3.8b --stages 4 \
        --microbatches 8 --requests 32 --seq 64 [--compress]

``build_pipeline_lm`` is the reusable bridge: any ModelConfig ->
(stage weights, unit_fn, head/tail fns) consumable by core.pipeline.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compile_cache import enable_compile_cache
from repro.configs.base import ModelConfig
from repro.configs.registry import ARCHS, get_config, get_smoke
from repro.core.pipeline import PipelineConfig, make_pipeline, stack_stages
from repro.kernels import ops as kops
from repro.launch.mesh import make_auto_mesh
from repro.models import layers as L
from repro.models import transformer as T


@dataclasses.dataclass
class PipelineLM:
    cfg: ModelConfig
    pipe_cfg: PipelineConfig
    stage_params: Any            # (stacked units, valid mask), stage-sharded
    extra: Any                   # replicated pytree (shared block) or None
    params: Any                  # embed/head/rem (they live outside the chain)
    fn: Callable                 # the sharded pipeline callable

    def __call__(self, tokens: jax.Array, prefix_embeds=None,
                 encoder_embeds=None) -> jax.Array:
        """tokens [B, S] with B = M * mb -> logits [B, S, V]."""
        cfg, M = self.cfg, self.pipe_cfg.num_microbatches
        B, S = tokens.shape
        assert B % M == 0, f"batch {B} must be M={M} microbatches"
        mb = B // M
        x = L.embed(self.params["embed"], tokens)
        x = T._fuse_prefix(cfg, x, prefix_embeds)

        if cfg.encoder_layers:
            enc_out, _ = T._encode(self.params, cfg, encoder_embeds)
            stream = {"h": x.reshape(M, mb, S, -1),
                      "enc": enc_out.reshape(M, mb, *enc_out.shape[1:])}
        else:
            stream = x.reshape(M, mb, S, -1)

        out = (self.fn(self.stage_params, stream) if self.extra is None
               else self.fn(self.stage_params, stream, self.extra))
        x = (out["h"] if isinstance(out, dict) else out).reshape(B, S, -1)

        # remainder layers + head run dispatcher-side (the tail of the chain)
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        _, rem = divmod(cfg.num_layers, cfg.unit_layers)[0], \
            cfg.num_layers % cfg.unit_layers
        if rem:
            aux = jnp.zeros((), jnp.float32)
            for i in range(rem):
                up = T._tree_at(self.params["rem"], i)
                x, aux = T._apply_layer(up["pos0"], cfg, x, positions, aux,
                                        T._window_at(cfg, i))
        x = L.rmsnorm(self.params["final_ln"], x, cfg.norm_eps)
        logits = (L.unembed(self.params["embed"], x) if cfg.tie_embeddings
                  else L.linear(self.params["unembed"], x))
        return T._mask_pad_vocab(cfg, logits)


def make_unit_fn(cfg: ModelConfig, with_extra: bool, unroll: bool = False):
    """Masked multi-unit stage body over ``T._apply_unit``."""

    def apply_unit(up, x, extra):
        if isinstance(x, dict):
            h, enc = x["h"], x["enc"]
        else:
            h, enc = x, None
        B, S, _ = h.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        aux = jnp.zeros((), jnp.float32)
        shared = extra.get("shared") if extra else None
        h, _ = T._apply_unit(up, cfg, h, positions, aux, shared=shared,
                             enc_out=enc)
        return {"h": h, "enc": enc} if isinstance(x, dict) else h

    def stage_fn(local, x, extra=None):
        units, valid = local

        def body(hh, inp):
            up, ok = inp
            y = apply_unit(up, hh, extra)
            keep = jax.tree_util.tree_map(
                lambda a, b: jnp.where(ok, a, b), y, hh)
            return keep, None

        u = jax.tree_util.tree_leaves(units)[0].shape[0]
        out, _ = jax.lax.scan(body, x, (units, valid),
                              unroll=u if unroll else 1)
        return out

    if with_extra:
        return stage_fn
    return lambda local, x: stage_fn(local, x, None)


def init_stage_params(cfg: ModelConfig, key: jax.Array, mesh,
                      num_stages: int, dtype=jnp.float32):
    """Initialise an LM for the stage pipeline with its unit stack created
    already stacked per stage and sharded over ``"stage"``: each device
    materialises only its own stage's units, so no device ever holds the
    whole model (nor a second, stacked copy of it).

    Returns ``(params, stage_params)``: ``params`` is everything but
    ``"units"``, replicated; ``stage_params`` is ``stack_stages``' pair,
    for :func:`build_pipeline_lm`.  The values equal
    ``T.init_lm(cfg, key, dtype)``'s: JAX's random bits do not depend on
    how the output is sharded.
    """
    n_units = cfg.num_layers // cfg.unit_layers

    def init(k):
        params = T.init_lm(cfg, k, dtype)
        return params, stack_stages(params.pop("units"), n_units, num_stages)

    rest, staged = jax.eval_shape(init, key)
    tmap = jax.tree_util.tree_map
    shardings = (tmap(lambda _: NamedSharding(mesh, P()), rest),
                 tmap(lambda _: NamedSharding(mesh, P("stage")), staged))
    return jax.jit(init, out_shardings=shardings)(key)


def build_pipeline_lm(cfg: ModelConfig, params: Any, mesh,
                      num_stages: int, num_microbatches: int,
                      compress: bool = False, quant_impl: str = "jnp",
                      axis: str = "stage",
                      data_axes: tuple[str, ...] = (),
                      unroll: bool = False,
                      stage_params: Any = None) -> PipelineLM:
    """``stage_params`` comes from :func:`init_stage_params`; without it
    the stages are stacked from ``params["units"]``."""
    if stage_params is None:
        n_units = cfg.num_layers // cfg.unit_layers
        stage_params = stack_stages(params["units"], n_units, num_stages)
    extra = {"shared": params["shared"]} if "shared" in params else None
    pipe_cfg = PipelineConfig(num_stages=num_stages,
                              num_microbatches=num_microbatches,
                              axis=axis, compress=compress,
                              quant_impl=quant_impl, unroll_ticks=unroll)
    fn = make_pipeline(mesh, pipe_cfg,
                       make_unit_fn(cfg, extra is not None, unroll=unroll),
                       data_axes=data_axes, with_extra=extra is not None)
    return PipelineLM(cfg, pipe_cfg, stage_params, extra, params, fn)


# -- autoregressive decode THROUGH the pipeline (beyond-paper) -------------------

def build_pipeline_decoder(cfg: ModelConfig, params: Any, mesh,
                           num_stages: int, num_microbatches: int, mb: int,
                           max_len: int, steps: int, compress: bool = False,
                           axis: str = "stage"):
    """Decode pipeline: returns (fn, stage_params, caches0, head).

    fn(stage_params, caches, start_tok [M,mb,1], start_pos [M,mb])
        -> (tokens [M, steps, mb], new_caches)
    """
    from repro.core.pipeline import stack_stages
    from repro.core.pipeline_decode import make_pipeline_decoder

    assert cfg.num_layers % cfg.unit_layers == 0, \
        "decode pipeline needs an integral unit stack (no remainder layers)"
    n_units = cfg.num_layers // cfg.unit_layers
    stacked, valid = stack_stages(params["units"], n_units, num_stages)

    # per-microbatch cache slabs: [n_units, M, mb, ...] -> [S, u, M, mb, ...]
    M = num_microbatches
    base = T.init_caches(cfg, mb, max_len, jnp.float32)

    def stack_m(a):
        return jnp.broadcast_to(a[:, None], (a.shape[0], M) + a.shape[1:])

    unit_caches = jax.tree_util.tree_map(stack_m, base["units"])
    caches0, _ = stack_stages(unit_caches, n_units, num_stages)

    head = {"embed": params["embed"], "final_ln": params["final_ln"]}
    if not cfg.tie_embeddings:
        head["unembed"] = params["unembed"]
    if "shared" in params:
        head["shared"] = params["shared"]

    def embed_fn(hd, tok):
        return L.embed(hd["embed"], tok)

    def head_fn(hd, h):
        x = L.rmsnorm(hd["final_ln"], h, cfg.norm_eps)
        logits = (L.unembed(hd["embed"], x) if cfg.tie_embeddings
                  else L.linear(hd["unembed"], x))
        return T._mask_pad_vocab(cfg, logits)

    def decode_unit_fn(local_w, h, pos, mcache, hd):
        units, vmask = local_w
        shared = hd.get("shared")

        def body(carry, inp):
            hh = carry
            (up, ok), uc = inp
            h2 = hh
            ncs = {}
            for i in range(cfg.unit_layers):
                h2, nc = T._decode_layer(up[f"pos{i}"], cfg, h2, pos,
                                         uc[f"pos{i}"], T._window_at(cfg, i),
                                         None, False)
                ncs[f"pos{i}"] = nc
            if shared is not None:
                sc = uc["shared"]
                from repro.models import attention as attn_mod
                s = T.attn_spec(cfg, None)
                h2, nkv, nkpos = attn_mod.attention_decode(
                    shared["attn"], s, h2, pos, sc, sc["kpos"], cfg.norm_eps)
                h2 = L.mlp(shared["mlp"], h2, cfg.norm_eps)
                ncs["shared"] = {**nkv, "kpos": nkpos}
            hh_out = jnp.where(ok, h2, hh)
            ncs = jax.tree_util.tree_map(
                lambda n, o: jnp.where(ok, n, o), ncs, uc)
            return hh_out, ncs

        h_out, new_caches = jax.lax.scan(body, h, ((units, vmask), mcache))
        return h_out, new_caches

    pipe_cfg = PipelineConfig(num_stages=num_stages, num_microbatches=M,
                              axis=axis, compress=compress)
    fn = make_pipeline_decoder(mesh, pipe_cfg, decode_unit_fn=decode_unit_fn,
                               embed_fn=embed_fn, head_fn=head_fn,
                               steps=steps)
    return fn, (stacked, valid), caches0, head


def wire_bytes_per_relay(cfg: ModelConfig, mb: int, seq: int,
                         compress: bool) -> int:
    """Bytes one stage relays per microbatch (the paper's 'data' payload)."""
    shape = (mb * seq, cfg.d_model)
    if not compress:
        return mb * seq * cfg.d_model * 2          # bf16
    raw, wire = kops.quant_bytes(shape, jnp.bfloat16)
    return wire


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), required=True)
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch) if args.full else get_smoke(args.arch)
    if jax.device_count() < args.stages:
        raise SystemExit(
            f"--stages {args.stages} needs one device per stage; "
            f"{jax.device_count()} {jax.default_backend()} device(s) found "
            f"(pass --stages {jax.device_count()} or fewer)")
    mesh = make_auto_mesh((args.stages,), ("stage",))
    params, stage_params = init_stage_params(cfg, jax.random.PRNGKey(0),
                                             mesh, args.stages)
    lm = build_pipeline_lm(cfg, params, mesh, args.stages, args.microbatches,
                           compress=args.compress, stage_params=stage_params)
    B = args.requests
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, args.seq), 0,
                                cfg.vocab)
    kw = {}
    if cfg.num_prefix_embeds and not cfg.encoder_layers:
        kw["prefix_embeds"] = jnp.zeros((B, cfg.num_prefix_embeds, cfg.d_model))
    if cfg.encoder_layers:
        kw["encoder_embeds"] = jnp.zeros((B, cfg.num_prefix_embeds,
                                          cfg.d_model))
    with mesh:
        run = jax.jit(lambda t: lm(t, **kw))
        logits = run(tokens)
        logits.block_until_ready()
        t0 = time.perf_counter()
        logits = run(tokens)
        logits.block_until_ready()
        dt = time.perf_counter() - t0
    mb = B // args.microbatches
    wire = wire_bytes_per_relay(cfg, mb, args.seq, args.compress)
    print(f"arch={args.arch} stages={args.stages} M={args.microbatches} "
          f"compress={args.compress}")
    print(f"logits {logits.shape}; wall {dt*1e3:.1f} ms; "
          f"relay payload/microbatch {wire/1e6:.3f} MB")


if __name__ == "__main__":
    main()
