"""Autoregressive decoding THROUGH the DEFER pipeline (beyond-paper).

The paper pipelines independent inference samples; autoregressive LMs add a
twist the paper never faced: token t+1 cannot enter the chain until token t
leaves it.  A naive chain would idle S-1 of S stages.  The fix is the
paper's own FIFO insight applied to decode: keep M >= S *microbatches* (groups
of sequences) in flight — while microbatch m's token is at stage s,
microbatch m+1's token is at stage s-1.  The generated token ppermutes from
the LAST stage straight back to stage 0 on the same circular ring that
relays hidden states, so the dispatcher round-trip of the original
architecture disappears entirely: steady-state emits one token per tick per
microbatch with zero host involvement.

Schedule: tick t, stage s serves microbatch m = (t-s) mod M at decode step
p = (t-s) div M (valid while 0 <= t-s < M*steps).  Per-stage state: the
KV/SSM caches of its own units for ALL M microbatches (leading dim M).

The relayed payload is a pytree {h, tok, logit_tok}: stages 1..S-1 consume
``h``; stage 0 consumes ``tok`` (the token the last stage just sampled) and
embeds it.  With ``compress=True`` the hidden ``h`` rides the int8 wire.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.pipeline import PipelineConfig, _wire_decode, _wire_encode

tmap = jax.tree_util.tree_map


def pipeline_decode_apply(stage_params: Any, caches: Any, start_tok: Any,
                          start_pos: Any, head: Any, *,
                          decode_unit_fn: Callable, embed_fn: Callable,
                          head_fn: Callable, steps: int,
                          cfg: PipelineConfig):
    """Per-device body (under shard_map over ``cfg.axis``).

    stage_params: (units [1, u, ...], valid [1, u]) local slice.
    caches: local unit caches, leaves [1, u, M, ...].
    start_tok [M, mb, 1] int32; start_pos [M, mb] int32.
    head: replicated embed/final-norm/unembed params.
    Returns (tokens [M, steps, mb], final caches local slice).
    """
    S, M = cfg.num_stages, cfg.num_microbatches
    axis = cfg.axis
    sid = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % S) for i in range(S)]
    local_w = tmap(lambda a: a[0], stage_params)
    local_caches = tmap(lambda a: a[0], caches)       # [u, M, ...]
    mb = start_tok.shape[1]
    d = None  # hidden dim from embed

    def relay(y):
        if not cfg.compress:
            return tmap(lambda a: jax.lax.ppermute(a, axis, perm), y)

        def one(a):
            if a.dtype in (jnp.int32, jnp.uint32) or a.ndim < 2:
                return jax.lax.ppermute(a, axis, perm)
            q, sc = _wire_encode(a, cfg.quant_impl)
            q = jax.lax.ppermute(q, axis, perm)
            sc = jax.lax.ppermute(sc, axis, perm)
            return _wire_decode(q, sc, a.shape, a.dtype, cfg.quant_impl)

        return tmap(one, y)

    total = M * steps + S - 1

    def tick(carry, t):
        state, tok_buf, cach, outbuf = carry
        # 1. bank the arriving wrapped token: the payload reaching stage 0 at
        # tick t left the last stage at t-1, which served k_arr = t-S, i.e.
        # microbatch (t-S) mod M.  A single relay slot would be overwritten
        # over the M-S idle ticks before that microbatch's next turn, so
        # stage 0 keeps a per-microbatch token buffer.
        k_arr = t - S
        m_arr = jnp.clip(k_arr % M, 0, M - 1)
        arr_cur = jax.lax.dynamic_index_in_dim(tok_buf, m_arr, 0, False)
        tok_buf = jax.lax.dynamic_update_index_in_dim(
            tok_buf, jnp.where(k_arr >= 0, state["tok"], arr_cur), m_arr, 0)

        # 2. which microbatch / decode step this stage serves now
        k = t - sid
        valid = (k >= 0) & (k < M * steps)
        m = jnp.clip(k % M, 0, M - 1)
        p = jnp.clip(k // M, 0, steps - 1)

        tok_in = jnp.where(
            k < M,                                     # first round: prompt
            jax.lax.dynamic_index_in_dim(start_tok, m, 0, False),
            jax.lax.dynamic_index_in_dim(tok_buf, m, 0, False))
        pos_in = jax.lax.dynamic_index_in_dim(start_pos, m, 0, False) + p

        h_in = jnp.where(sid == 0, embed_fn(head, tok_in), state["h"])
        mcache = tmap(lambda a: jax.lax.dynamic_index_in_dim(a, m, 1, False),
                      cach)                            # [u, ...]
        h_out, new_mcache = decode_unit_fn(local_w, h_in, pos_in, mcache,
                                           head)
        # only commit the cache when this tick is real
        new_mcache = tmap(lambda n, o: jnp.where(valid, n, o), new_mcache,
                          mcache)
        cach = tmap(lambda a, nm: jax.lax.dynamic_update_index_in_dim(
            a, nm, m, 1), cach, new_mcache)

        # last stage: head + greedy sample
        logits = head_fn(head, h_out)                  # [mb, 1, V]
        new_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [mb, 1]

        # record the token this stage just produced (only last stage real)
        write = valid & (sid == S - 1)
        cur = jax.lax.dynamic_slice(outbuf, (m, p, 0), (1, 1, mb))
        upd = jnp.where(write, new_tok[None, :, 0][:, None], cur)
        outbuf = jax.lax.dynamic_update_slice(outbuf, upd, (m, p, 0))

        nxt = relay({"h": h_out, "tok": new_tok})
        return (nxt, tok_buf, cach, outbuf), None

    h0 = embed_fn(head, start_tok[0])                  # shape donor
    state0 = {"h": jnp.zeros_like(h0),
              "tok": jnp.zeros((mb, 1), jnp.int32)}
    tok_buf0 = jnp.zeros((M, mb, 1), jnp.int32)
    out0 = jnp.zeros((M, steps, mb), jnp.int32)
    (_, _, final_caches, outbuf), _ = jax.lax.scan(
        tick, (state0, tok_buf0, local_caches, out0), jnp.arange(total))
    return outbuf, tmap(lambda a: a[None], final_caches)


def make_pipeline_decoder(mesh: Mesh, cfg: PipelineConfig, *,
                          decode_unit_fn, embed_fn, head_fn, steps: int):
    """Sharded decode-pipeline callable.

    fn(stage_params, caches, start_tok, start_pos, head)
      -> (tokens [M, steps, mb], new caches)

    stage_params leaves [S, u, ...]; caches leaves [S, u, M, ...] — both
    sharded over the stage axis.  ``head`` (embed/norm/unembed) replicated.
    """
    pspec_w = P(cfg.axis)

    def per_device(w, cach, tok, pos, head):
        toks, new_c = pipeline_decode_apply(
            w, cach, tok, pos, head, decode_unit_fn=decode_unit_fn,
            embed_fn=embed_fn, head_fn=head_fn, steps=steps, cfg=cfg)
        return toks[None], new_c

    sharded = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(pspec_w, pspec_w, P(), P(), P()),
        out_specs=(P(cfg.axis), pspec_w),
        check_vma=False)

    def fn(stage_params, caches, start_tok, start_pos, head):
        toks, new_c = sharded(stage_params, caches, start_tok, start_pos,
                              head)
        return toks[-1], new_c                  # last stage's token record

    return fn
