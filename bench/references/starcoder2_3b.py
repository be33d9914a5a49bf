"""Plain reference of the decoder that ``bench/configs/starcoder2-3b.json``
states: a full causal forward pass over a whole sequence, no KV cache, no
batching, no wire, no partition.

It takes the benchmark's weights (``bench/weights.py``), keyed as the
program names its layers (``embed``, ``blk<i>_attn``, ``blk<i>_mlp``,
``head``), and imports nothing of the program.  Per layer: RMSNorm, GQA
attention with rotate-half RoPE (GPT-NeoX form), a residual add, RMSNorm,
a tanh-approximated GELU MLP and a residual add; then RMSNorm and the
untied head.

Every sequence is padded to the KV capacity, so one compiled program
serves every length (padding sits after the tokens and causal masking
keeps it out of them).  ``precision`` says how it computes:

- ``float32``: every matmul at ``highest`` precision: the reference;
- ``bfloat16``: weights and activations in bf16 at the default precision;
- ``int8``: f32 arithmetic on matmul operands rounded to int8, each by its
  own absmax scale (symmetric, 127 steps; per row of activations, per
  tensor of weights).

The last two are controls (``bench/check.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

Q_CHUNK = 512   # queries per attention block


def int8_round(a, axis=None):
    """``a`` rounded to 127 symmetric steps of its absmax over ``axis``."""
    s = jnp.maximum(jnp.abs(a).max(axis=axis, keepdims=axis is not None),
                    1e-30) / 127.0
    return jnp.round(a / s) * s


def _mm(x, w, rnd):
    return rnd(x, -1) @ rnd(w, None)


def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """x [T, heads, hd] at positions 0..T-1."""
    t, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _attention(q, k, v):
    """Causal GQA: q [T, H, hd], k/v [T, KV, hd] -> [T, H*hd]."""
    t, h, hd = q.shape
    kv = k.shape[1]
    qg = q.reshape(t, kv, h // kv, hd)
    keys = jnp.arange(t)
    outs = []
    for s in range(0, t, Q_CHUNK):
        qc = qg[s:s + Q_CHUNK]
        lg = jnp.einsum("qkgd,skd->kgqs", qc, k).astype(jnp.float32)
        lg = lg / np.sqrt(hd)
        rows = jnp.arange(s, s + qc.shape[0])
        lg = jnp.where(rows[:, None] >= keys[None, :], lg, -1e30)
        w = jax.nn.softmax(lg, axis=-1).astype(v.dtype)
        outs.append(jnp.einsum("kgqs,skd->qkgd", w, v))
    return jnp.concatenate(outs).reshape(t, h * hd)


def _forward(params, tokens, *, cfg, rnd):
    eps, theta = cfg["norm_epsilon"], cfg["rope_theta"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    t = tokens.shape[0]
    x = params["embed"]["table"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        a = params[f"blk{i}_attn"]
        y = _rmsnorm(x, a["ln"]["scale"], eps)
        q = _rope(_mm(y, a["wq"]["w"], rnd).reshape(t, h, hd), theta)
        k = _rope(_mm(y, a["wk"]["w"], rnd).reshape(t, kv, hd), theta)
        v = _mm(y, a["wv"]["w"], rnd).reshape(t, kv, hd)
        x = x + _mm(_attention(q, k, v), a["wo"]["w"], rnd)
        m = params[f"blk{i}_mlp"]
        y = _rmsnorm(x, m["ln"]["scale"], eps)
        up = jax.nn.gelu(_mm(y, m["up"]["w"], rnd), approximate=True)
        x = x + _mm(up, m["down"]["w"], rnd)
    hp = params["head"]
    return _mm(_rmsnorm(x, hp["ln"]["scale"], eps), hp["out"]["w"], rnd)


@functools.lru_cache(maxsize=None)
def _compiled(cfg_items: tuple, int8: bool):
    rnd = int8_round if int8 else (lambda a, axis=None: a)
    return jax.jit(functools.partial(_forward, cfg=dict(cfg_items), rnd=rnd))


def logits(params, cfg: dict, tokens, precision="float32") -> jax.Array:
    """Next-token logits [len(tokens), vocab], f32 on the device, at every
    position of ``tokens`` (row i predicts token i + 1)."""
    n = len(tokens)
    cap = cfg["max_position_embeddings"]
    if n > cap:
        raise ValueError(f"{n} tokens exceed the KV capacity {cap}")
    padded = np.zeros(cap, np.int32)
    padded[:n] = tokens
    dtype = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    fwd = _compiled(tuple(sorted((k, v) for k, v in cfg.items()
                                 if isinstance(v, (int, float, str)))),
                    precision == "int8")
    with jax.default_matmul_precision(
            "default" if precision == "bfloat16" else "highest"):
        out = fwd(p, jnp.asarray(padded))
    return out[:n].astype(jnp.float32)
