"""A decode-capable language model as a partitionable :class:`LayerGraph`.

This is the bridge between the model zoo's attention/MLP/Mamba2 primitives
and the serving runtime's autoregressive session path: every mixer block
carries a :class:`~repro.core.graph.LayerDecode` — an attention block's
prefill builds the fixed-capacity KV cache, a Mamba2 block's returns its
conv and SSD state, and each step consumes one token against that state —
and every other block is stateless token-wise compute whose ``fn`` already
works at ``S=1``.  One graph may mix the two kinds of mixer (a hybrid such
as Granite-4.0-H), so one session's state is a KV cache in some layers and
a fixed-size recurrent state in others.  The
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

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import SSMConfig
from repro.core.graph import LayerDecode, LayerGraph, SlabRows
from repro.models.attention import (AttnSpec, attention, attention_decode,
                                    attention_decode_slots, attn_flops,
                                    packed_cache)
from repro.models.layers import apply_rope, linear, mlp, mlp_flops, rmsnorm
from repro.models.ssm import (MambaSpec, mamba_flops, mamba_mixer,
                              mamba_mixer_slots, mamba_mixer_step)


def _attn_nodes(spec: AttnSpec, cache_len: int, use_kernel: bool):
    """(fn, prefill, step) closures for one attention block."""
    packed = packed_cache(spec)

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
        if spec.rope:
            k = apply_rope(k, positions, spec.rope_theta)
        shape = (B, cache_len, spec.kv_heads, spec.head_dim)
        if packed:              # each position's heads as one lane-dense row
            shape = (B, cache_len, spec.kv_heads * spec.head_dim)
            k, v = k.reshape(B, S, -1), v.reshape(B, S, -1)
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
        k, v = cache["k"], cache["v"]
        if packed:
            k = k.reshape(k.shape[:2] + (spec.kv_heads, spec.head_dim))
            v = v.reshape(k.shape)
        out, kv, kpos = attention_decode(
            p, spec, x, pos, {"k": k, "v": v}, cache["kpos"],
            use_kernel=use_kernel)
        k, v = kv["k"], kv["v"]
        if packed:
            k, v = k.reshape(cache["k"].shape), v.reshape(cache["v"].shape)
        return out, {"k": k, "v": v, "kpos": kpos}

    return fn, prefill, step


def _residual_add(residual: float):
    """``x + residual * y``, the block output ``y`` on the residual
    stream ``x``."""
    return lambda x, y: x + (y if residual == 1.0 else residual * y)


def _mamba_nodes(spec: MambaSpec, residual: float, use_kernel: bool):
    """(fn, prefill, step) closures for one Mamba2 block: the mixer's
    output, times ``residual``, added to the residual stream.  Prefill
    runs the chunked SSD (``ssd_scan`` with ``use_kernel``) and returns
    the conv and SSD state; the step reads and writes a wave's rows of
    them in the slab by slot (``ssm_step`` with ``use_kernel``)."""
    add = _residual_add(residual)

    def fn(p, x):
        return add(x, mamba_mixer(p, spec, x, use_kernel=use_kernel)[0])

    def prefill(p, x):
        y, cache = mamba_mixer(p, spec, x, use_kernel=use_kernel)
        return add(x, y), cache

    def step(p, cache, x, pos):
        del pos                        # the state holds the whole history
        if isinstance(cache, SlabRows):
            y, slab = mamba_mixer_slots(p, spec, x, cache.slab, cache.slots,
                                        use_kernel=use_kernel)
            return add(x, y), SlabRows(slab, cache.slots)
        y, cache = mamba_mixer_step(p, spec, x, cache)
        return add(x, y), cache

    return fn, prefill, step


def _gated_mlp(residual: float):
    """Granite's shared MLP: ``W_out(silu(a) * b)`` with ``[a, b] = split(
    W_in h)``, ``h`` the RMS-normed input, added times ``residual``."""

    add = _residual_add(residual)

    def fn(p, x):
        a, b = jnp.split(linear(p["w_in"], rmsnorm(p["ln"], x)), 2, axis=-1)
        return add(x, linear(p["w_out"], jax.nn.silu(a) * b))

    return fn


def decode_lm_graph(vocab: int = 64, d_model: int = 32, n_layers: int = 2,
                    num_heads: int = 2, kv_heads: int = 2, head_dim: int = 16,
                    d_ff: int = 64, cache_len: int = 64, seq_hint: int = 8,
                    use_kernel: bool = False, dtype=np.float32, *,
                    layer_types: Sequence[str] | None = None,
                    mlp_kind: str = "gelu", rope: bool = True,
                    attn_scale: float | None = None,
                    embed_scale: float = 1.0, residual_scale: float = 1.0,
                    logits_scaling: float = 1.0,
                    ssm: SSMConfig | None = None) -> LayerGraph:
    """Build a decoder-only language model LayerGraph.

    Layer ``i`` is a mixer node ``blk<i>_attn`` (GQA attention) or
    ``blk<i>_mamba`` (a single-group Mamba2 of ``ssm``'s sizes), as
    ``layer_types[i]`` says (``"attention"`` or ``"mamba"``; default all
    attention), then an MLP node ``blk<i>_mlp``: ``"gelu"`` (``up``,
    ``down``) or ``"gated_silu"`` (Granite's, ``w_in`` ``[d, 2 d_ff]``,
    ``w_out``).  Each mixer and MLP adds its output times
    ``residual_scale`` to the residual stream; the embedding is scaled by
    ``embed_scale`` and the logits divided by ``logits_scaling``;
    attention takes RoPE unless ``rope`` is False and the softmax scale
    ``attn_scale`` (default ``1/sqrt(head_dim)``).  The defaults build the
    plain GELU decoder with RoPE.  Norms are RMSNorm with eps 1e-5.

    ``cache_len`` is the per-session KV capacity every attention block
    allocates at prefill — a graph-level constant, so every session's
    caches (leading axis 1) fit one row of a serving replica's KV slab and
    a decode wave specializes once per batch size; a Mamba2 block's state
    is the same size at any position.  ``seq_hint`` only sizes the
    nominal out_specs the partitioner costs cuts with.
    """
    types = list(layer_types) if layer_types is not None \
        else ["attention"] * n_layers
    if len(types) != n_layers or set(types) - {"attention", "mamba"}:
        raise ValueError(f"layer_types {types} must name 'attention' or "
                         f"'mamba' for each of {n_layers} layers")
    if mlp_kind not in ("gelu", "gated_silu"):
        raise ValueError(f"unknown mlp_kind {mlp_kind!r}")
    if "mamba" in types and ssm is None:
        raise ValueError("Mamba2 layers need their sizes (ssm)")
    spec = AttnSpec(d_model=d_model, num_heads=num_heads, kv_heads=kv_heads,
                    head_dim=head_dim, rope=rope, scale=attn_scale,
                    residual=residual_scale)
    mspec = MambaSpec(d_model, ssm) if "mamba" in types else None
    f32 = dtype
    sds = jax.ShapeDtypeStruct
    g = LayerGraph(f"lm-{n_layers}x{d_model}", sds((1, seq_hint), np.int32))
    act_spec = sds((1, seq_hint, d_model), f32)
    norm = lambda n: {"scale": sds((n,), f32)}

    embed = (lambda p, x: p["table"][x]) if embed_scale == 1.0 else \
        (lambda p, x: p["table"][x] * embed_scale)
    g.layer("embed", embed, {"table": sds((vocab, d_model), f32)},
            ("",), act_spec, flops=0.0, pad_safe=True)
    prev = "embed"
    for i, kind in enumerate(types):
        if kind == "attention":
            fn, prefill, step = _attn_nodes(spec, cache_len, use_kernel)
            mixer = f"blk{i}_attn"
            g.layer(mixer, fn,
                    {"ln": norm(d_model),
                     "wq": {"w": sds((d_model, num_heads * head_dim), f32)},
                     "wk": {"w": sds((d_model, kv_heads * head_dim), f32)},
                     "wv": {"w": sds((d_model, kv_heads * head_dim), f32)},
                     "wo": {"w": sds((num_heads * head_dim, d_model), f32)}},
                    (prev,), act_spec,
                    flops=attn_flops(spec, seq_hint, seq_hint),
                    pad_safe=False,
                    decode=LayerDecode(prefill_fn=prefill, step_fn=step))
        else:
            fn, prefill, step = _mamba_nodes(mspec, residual_scale,
                                             use_kernel)
            mixer = f"blk{i}_mamba"
            di, n, h = mspec.d_inner, ssm.state_dim, mspec.n_heads
            ch = mspec.conv_channels
            g.layer(mixer, fn,
                    {"ln": norm(d_model),
                     "in_proj": sds((d_model, 2 * di + 2 * n + h), f32),
                     "conv_w": sds((ssm.conv_width, ch), f32),
                     "conv_b": sds((ch,), f32),
                     "A_log": sds((h,), f32), "D": sds((h,), f32),
                     "dt_bias": sds((h,), f32),
                     "norm": norm(di),
                     "out_proj": sds((di, d_model), f32)},
                    (prev,), act_spec, flops=mamba_flops(mspec, seq_hint),
                    pad_safe=False,
                    decode=LayerDecode(prefill_fn=prefill, step_fn=step,
                                       recurrent=True))
        if mlp_kind == "gelu":
            g.layer(f"blk{i}_mlp", lambda p, x: mlp(p, x),
                    {"ln": norm(d_model),
                     "up": {"w": sds((d_model, d_ff), f32)},
                     "down": {"w": sds((d_ff, d_model), f32)}},
                    (mixer,), act_spec,
                    flops=mlp_flops(d_model, d_ff, False, seq_hint),
                    pad_safe=True)
        else:
            g.layer(f"blk{i}_mlp", _gated_mlp(residual_scale),
                    {"ln": norm(d_model),
                     "w_in": {"w": sds((d_model, 2 * d_ff), f32)},
                     "w_out": {"w": sds((d_ff, d_model), f32)}},
                    (mixer,), act_spec,
                    flops=mlp_flops(d_model, d_ff, True, seq_hint),
                    pad_safe=True)
        prev = f"blk{i}_mlp"
    head = (lambda p, x: linear(p["out"], rmsnorm(p["ln"], x)))
    if logits_scaling != 1.0:
        head = (lambda p, x: linear(p["out"], rmsnorm(p["ln"], x))
                / logits_scaling)
    g.layer("head", head,
            {"ln": norm(d_model), "out": {"w": sds((d_model, vocab), f32)}},
            (prev,), sds((1, seq_hint, vocab), f32),
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
