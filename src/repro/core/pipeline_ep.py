"""Pipeline + expert parallelism (PP x EP x DP) — §Perf HC2 iteration 3.

Big-MoE serving (llama4-maverick 400B, dbrx 132B) cannot keep a stage's
full expert set on one chip, and GSPMD's global-view dispatch all-reduces
whole capacity buffers (measured 2.25 TB/device for llama4 prefill).  The
production layout is the DEFER chain *per group of chips*:

    mesh = (data, expert, stage)        e.g. (2, 8, 16) = 256 chips

* stage  — the paper's compute-node chain (ppermute relays, microbatches)
* expert — within a stage: attention is head-sharded TP (one psum/layer),
           MoE is GShard expert parallelism (explicit all_to_all of routed
           tokens via ``moe_block_local``)
* data   — replicated chains (DEFER's parallel inference jobs)

Everything is explicit shard_map code — no GSPMD guessing.  Per layer the
exchanged bytes are one [mb,S,d] psum + one token all-gather + two
token-capacity all_to_alls, instead of full-buffer all-reduces.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.pipeline import PipelineConfig, pipeline_apply, stack_stages
from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.models import transformer as T
from repro.models.attention import AttnSpec, _chunked_attention, apply_rope

tmap = jax.tree_util.tree_map


def ep_unit_fn(cfg: ModelConfig, expert_axis: str = "expert",
               unroll: bool = False):
    """Stage body: per-device code with head-TP attention + EP MoE."""
    spec = T.moe_spec(cfg)
    scale = 1.0 / np.sqrt(cfg.head_dim)

    def apply_layer(lp, x):
        ax = jax.lax.axis_size(expert_axis)
        mb, S, d = x.shape
        pos = jnp.broadcast_to(jnp.arange(S)[None], (mb, S))
        # -- attention, heads sharded over the expert axis ---------------
        h = L.rmsnorm(lp["attn"]["ln"], x, cfg.norm_eps)
        Hl = cfg.num_heads // ax
        kvl = max(1, cfg.kv_heads // ax)
        q = (h @ lp["attn"]["wq"]["w"]).reshape(mb, S, Hl, cfg.head_dim)
        k = (h @ lp["attn"]["wk"]["w"]).reshape(mb, S, kvl, cfg.head_dim)
        v = (h @ lp["attn"]["wv"]["w"]).reshape(mb, S, kvl, cfg.head_dim)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
        s_local = AttnSpec(d, Hl, kvl, cfg.head_dim)       # local-head view
        C = min(s_local.q_chunk, S)
        if S % C:
            C = S
        qs = q.reshape(mb, S // C, C, Hl, cfg.head_dim)
        pos_q = pos.reshape(mb, S // C, C)
        o = _chunked_attention(qs, k, v, pos_q, pos, s_local, scale, C)
        o = o.reshape(mb, S, Hl * cfg.head_dim) @ lp["attn"]["wo"]["w"]
        x = x + jax.lax.psum(o, expert_axis)               # partial over heads
        # -- MoE, tokens split over the expert axis, GShard a2a ----------
        i = jax.lax.axis_index(expert_axis)
        T_tot = mb * S
        T_l = T_tot // ax
        x_flat = x.reshape(T_tot, d)
        x_l = jax.lax.dynamic_slice_in_dim(x_flat, i * T_l, T_l)[None]
        y_l, _ = moe_mod.moe_block_local(lp["moe"], spec, x_l, expert_axis,
                                         cfg.norm_eps)
        y = jax.lax.all_gather(y_l[0], expert_axis, tiled=True)  # [T, d]
        return y.reshape(mb, S, d)

    def stage_fn(local, x):
        units, valid = local

        def body(hh, inp):
            up, ok = inp
            y = apply_layer(up["pos0"], hh)
            return jnp.where(ok, y, hh), None

        u = jax.tree_util.tree_leaves(units)[0].shape[0]
        out, _ = jax.lax.scan(body, x, (units, valid),
                              unroll=u if unroll else 1)
        return out

    return stage_fn


def _ep_weight_specs(units: Any, stage_axis: str, expert_axis: str):
    """Per-leaf specs: [S, u, ...] with head/expert dims over the EP axis."""
    def spec(path, leaf):
        keys = [getattr(k, "key", str(k)) for k in path]
        name = keys[-1]
        nd = len(leaf.shape)
        if "moe" in keys and name in ("up", "gate", "down"):
            return P(stage_axis, None, expert_axis, *([None] * (nd - 3)))
        if name == "w" and "wo" in keys:
            return P(stage_axis, None, expert_axis, None)
        if name == "w" and any(k in keys for k in ("wq", "wk", "wv")):
            return P(stage_axis, None, None, expert_axis)
        return P(stage_axis, *([None] * (nd - 1)))

    return jax.tree_util.tree_map_with_path(spec, units)


def build_ep_pipeline(cfg: ModelConfig, mesh: Mesh, num_stages: int,
                      num_microbatches: int, compress: bool = False,
                      unroll: bool = False,
                      data_axes: tuple[str, ...] = ("data",),
                      expert_axis: str = "expert",
                      stage_axis: str = "stage"):
    """Returns fn(units_stacked_valid, x_mb) -> y_mb for MoE decoder archs.

    ``units_stacked_valid`` = stack_stages(params["units"], ...); weights
    must be sliced per EP shard by the in_specs below (sharded arrays in,
    local shards inside).
    """
    pipe_cfg = PipelineConfig(num_stages=num_stages,
                              num_microbatches=num_microbatches,
                              axis=stage_axis, compress=compress,
                              unroll_ticks=unroll)
    unit_fn = ep_unit_fn(cfg, expert_axis, unroll=unroll)

    def per_device(w, x):
        out = pipeline_apply(w, x, unit_fn=unit_fn, cfg=pipe_cfg)
        return tmap(lambda a: a[None], out)

    def fn_factory(units_stacked, valid):
        w_specs = (_ep_weight_specs(units_stacked, stage_axis, expert_axis),
                   P(stage_axis))
        pspec_x = P(None, data_axes)
        pspec_y = P(stage_axis, None, data_axes)
        sharded = jax.shard_map(per_device, mesh=mesh,
                                in_specs=(w_specs, pspec_x),
                                out_specs=pspec_y, check_vma=False)

        def fn(w, x_mb):
            return tmap(lambda a: a[-1], sharded(w, x_mb))

        return fn

    return fn_factory
