"""A decode-capable transformer as a partitionable :class:`LayerGraph`.

This is the bridge between the model zoo's attention/MLP primitives and the
serving runtime's autoregressive session path: every attention block carries
a :class:`~repro.core.graph.LayerDecode` (prefill builds the fixed-capacity
KV cache, step consumes one token against it), every other block is
stateless token-wise compute whose ``fn`` already works at ``S=1``.  The
graph is a pure chain, so any contiguous partition has exactly one boundary
activation — a decode step ships ``[1, 1, d_model]`` per hop instead of the
full sequence.

Greedy decode through the distributed chain is bit-identical to
:func:`pipeline_decode_reference` below because both run the very same
``prefill_fn``/``step_fn`` per layer — the reference on one session's
cache, the serving node on the session's row of its KV slab (a
:class:`~repro.core.graph.SlabRows`), the same values either way;
batching sessions along axis 0 does not change per-row arithmetic.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import LayerDecode, LayerGraph, SlabRows
from repro.models.attention import (AttnSpec, attention, attention_decode,
                                    attention_decode_slots, attn_flops)
from repro.models.layers import apply_rope, linear, mlp, mlp_flops, rmsnorm


def _attn_nodes(spec: AttnSpec, cache_len: int, use_kernel: bool):
    """(fn, prefill, step) closures for one attention block."""

    def fn(p, x):
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        return attention(p, spec, x, positions)

    def prefill(p, x):
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        y = attention(p, spec, x, positions)
        # cache the prompt's K/V at slots [0, S) of the fixed-capacity
        # buffer (prompts longer than cache_len are rejected at session
        # open); kpos = -1 marks empty slots for the decode mask
        h = rmsnorm(p["ln"], x)
        k = linear(p["wk"], h).reshape(B, S, spec.kv_heads, spec.head_dim)
        v = linear(p["wv"], h).reshape(B, S, spec.kv_heads, spec.head_dim)
        k = apply_rope(k, positions, spec.rope_theta)
        shape = (B, cache_len, spec.kv_heads, spec.head_dim)
        ck = jnp.zeros(shape, x.dtype).at[:, :S].set(k)
        cv = jnp.zeros(shape, x.dtype).at[:, :S].set(v)
        kpos = jnp.full((B, cache_len), -1, jnp.int32).at[:, :S].set(
            jnp.arange(S, dtype=jnp.int32)[None, :])
        return y, {"k": ck, "v": cv, "kpos": kpos}

    def step(p, cache, x, pos):
        if isinstance(cache, SlabRows):
            out, slab = attention_decode_slots(p, spec, x, pos, cache.slab,
                                               cache.slots,
                                               use_kernel=use_kernel)
            return out, SlabRows(slab, cache.slots)
        out, kv, kpos = attention_decode(
            p, spec, x, pos, {"k": cache["k"], "v": cache["v"]},
            cache["kpos"], use_kernel=use_kernel)
        return out, {"k": kv["k"], "v": kv["v"], "kpos": kpos}

    return fn, prefill, step


def decode_lm_graph(vocab: int = 64, d_model: int = 32, n_layers: int = 2,
                    num_heads: int = 2, kv_heads: int = 2, head_dim: int = 16,
                    d_ff: int = 64, cache_len: int = 64, seq_hint: int = 8,
                    use_kernel: bool = False, dtype=np.float32) -> LayerGraph:
    """Build a small decoder-only transformer LayerGraph.

    ``cache_len`` is the per-session KV capacity every attention block
    allocates at prefill — a graph-level constant, so every session's
    caches (leading axis 1) fit one row of a serving replica's KV slab and
    a decode wave specializes once per batch size.  ``seq_hint`` only
    sizes the nominal out_specs the partitioner costs cuts with.
    """
    spec = AttnSpec(d_model=d_model, num_heads=num_heads, kv_heads=kv_heads,
                    head_dim=head_dim)
    f32 = dtype
    g = LayerGraph(f"lm-{n_layers}x{d_model}",
                   jax.ShapeDtypeStruct((1, seq_hint), np.int32))
    act_spec = jax.ShapeDtypeStruct((1, seq_hint, d_model), f32)

    g.layer("embed", lambda p, x: p["table"][x],
            {"table": jax.ShapeDtypeStruct((vocab, d_model), f32)},
            ("",), act_spec, flops=0.0, pad_safe=True)
    prev = "embed"
    for i in range(n_layers):
        fn, prefill, step = _attn_nodes(spec, cache_len, use_kernel)
        g.layer(f"blk{i}_attn", fn,
                {"ln": {"scale": jax.ShapeDtypeStruct((d_model,), f32)},
                 "wq": {"w": jax.ShapeDtypeStruct(
                     (d_model, num_heads * head_dim), f32)},
                 "wk": {"w": jax.ShapeDtypeStruct(
                     (d_model, kv_heads * head_dim), f32)},
                 "wv": {"w": jax.ShapeDtypeStruct(
                     (d_model, kv_heads * head_dim), f32)},
                 "wo": {"w": jax.ShapeDtypeStruct(
                     (num_heads * head_dim, d_model), f32)}},
                (prev,), act_spec,
                flops=attn_flops(spec, seq_hint, seq_hint),
                pad_safe=False,
                decode=LayerDecode(prefill_fn=prefill, step_fn=step))
        g.layer(f"blk{i}_mlp", lambda p, x: mlp(p, x),
                {"ln": {"scale": jax.ShapeDtypeStruct((d_model,), f32)},
                 "up": {"w": jax.ShapeDtypeStruct((d_model, d_ff), f32)},
                 "down": {"w": jax.ShapeDtypeStruct((d_ff, d_model), f32)}},
                (f"blk{i}_attn",), act_spec,
                flops=mlp_flops(d_model, d_ff, False, seq_hint),
                pad_safe=True)
        prev = f"blk{i}_mlp"
    g.layer("head", lambda p, x: linear(p["out"], rmsnorm(p["ln"], x)),
            {"ln": {"scale": jax.ShapeDtypeStruct((d_model,), f32)},
             "out": {"w": jax.ShapeDtypeStruct((d_model, vocab), f32)}},
            (prev,), jax.ShapeDtypeStruct((1, seq_hint, vocab), f32),
            flops=2.0 * seq_hint * d_model * vocab, pad_safe=True)
    # per-session KV capacity; the session layer enforces
    # len(prompt) + max_new_tokens <= decode_cache_len at open
    g.decode_cache_len = cache_len
    return g


def pipeline_decode_reference(graph: LayerGraph, params, prompt,
                              max_new_tokens: int) -> list[int]:
    """Single-device greedy decode through a decode-capable LayerGraph —
    the reference the distributed session path must match bit-for-bit.
    Runs the same per-layer ``prefill_fn``/``step_fn`` the compute nodes
    jit, just without partitioning, batching, or a wire."""
    acts = jnp.asarray(np.asarray(prompt, np.int32).reshape(1, -1))
    pos = acts.shape[1]
    caches: dict[str, object] = {}
    for node in graph.nodes:
        p = params[node.name]
        if node.decode is not None:
            acts, caches[node.name] = node.decode.prefill_fn(p, acts)
        else:
            acts = node.fn(p, acts)
    toks: list[int] = []
    while True:
        toks.append(int(np.argmax(np.asarray(acts[0, -1]))))
        if len(toks) >= max_new_tokens:
            return toks
        acts = jnp.asarray([[toks[-1]]], jnp.int32)
        pv = jnp.asarray([pos], jnp.int32)
        for node in graph.nodes:
            p = params[node.name]
            if node.decode is not None:
                acts, caches[node.name] = node.decode.step_fn(
                    p, caches[node.name], acts, pv)
            else:
                acts = node.fn(p, acts)
        pos += 1
