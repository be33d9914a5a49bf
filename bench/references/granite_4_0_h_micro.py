"""Plain reference of the Mamba2 + attention hybrid that
``bench/configs/granite-4.0-h-micro.json`` states (HF
``GraniteMoeHybridForCausalLM`` with no experts): a full causal forward
pass over a whole sequence, no cache, no batching, no wire, no partition.

It takes the benchmark's weights (``bench/weights.py``), keyed as the
program names its layers (``embed``, ``blk<i>_mamba`` or ``blk<i>_attn``,
``blk<i>_mlp``, ``head``), and imports nothing of the program.

- ``x = embed(tokens) * embedding_multiplier``;
- each of the first ``num_hidden_layers`` layers of ``layer_types``:
  ``x += residual_multiplier * mixer(rmsnorm(x))``, then ``x +=
  residual_multiplier * mlp(rmsnorm(x))``, with ``mlp(h) = W_out(silu(a)
  * b)``, ``[a, b] = split(W_in h)``;
- attention: causal GQA in blocks of queries, no position embedding, the
  softmax scaled by ``attention_multiplier``;
- Mamba2 (one group): ``in_proj -> [z, xBC, dt]``; a causal depthwise conv
  of width ``mamba_d_conv`` with bias, then SiLU, on ``xBC -> [x, B, C]``;
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the recurrence
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``
  run token by token (``lax.scan``), not in chunks as the program runs
  it; ``out_proj(rmsnorm(y * silu(z)))`` over all of the inner width;
- ``logits = head(rmsnorm(x)) / logits_scaling``.

Departures: the head is untied from the embedding, as the configuration
states; the recurrence's state is f32 at every precision below (the
program keeps it in f32), and its elementwise arithmetic takes no int8
rounding, like the attention's products (only the weight matmuls do).

Every sequence is padded to the KV capacity, so one compiled program
serves every length (padding sits after the tokens, and attention's
causal mask and the recurrence's order keep it out of them).
``precision`` says how it computes:

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
HEAD_ROWS = 2048  # positions per call of the head


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


def _attention(q, k, v, scale):
    """Causal GQA: q [T, H, hd], k/v [T, KV, hd] -> [T, H*hd]."""
    t, h, hd = q.shape
    kv = k.shape[1]
    qg = q.reshape(t, kv, h // kv, hd)
    keys = jnp.arange(t)
    outs = []
    for s in range(0, t, Q_CHUNK):
        qc = qg[s:s + Q_CHUNK]
        lg = jnp.einsum("qkgd,skd->kgqs", qc, k).astype(jnp.float32) * scale
        rows = jnp.arange(s, s + qc.shape[0])
        lg = jnp.where(rows[:, None] >= keys[None, :], lg, -1e30)
        w = jax.nn.softmax(lg, axis=-1).astype(v.dtype)
        outs.append(jnp.einsum("kgqs,skd->qkgd", w, v))
    return jnp.concatenate(outs).reshape(t, h * hd)


def _recurrence(xs, dt, a_log, bm, cm):
    """Token by token: xs [T, H, P], dt [T, H], bm/cm [T, N] -> y [T, H, P]
    (without the skip term), the state f32 [H, P, N] from zero."""
    A = -jnp.exp(a_log.astype(jnp.float32))

    def step(state, inp):
        x, d, b, c = inp
        state = state * jnp.exp(d * A)[:, None, None] \
            + (d[:, None] * x)[:, :, None] * b[None, None, :]
        return state, jnp.einsum("hpn,n->hp", state, c)

    h, p = xs.shape[1:]
    init = jnp.zeros((h, p, bm.shape[1]), jnp.float32)
    f32 = lambda a: a.astype(jnp.float32)
    _, y = jax.lax.scan(step, init, (f32(xs), f32(dt), f32(bm), f32(cm)))
    return y


def _mamba(m, y, cfg, rnd):
    t = y.shape[0]
    eps = cfg["rms_norm_eps"]
    hp, dh, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    di = hp * dh
    w = cfg["mamba_d_conv"]
    zxbcdt = _mm(y, m["in_proj"], rnd)
    z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * n],
                  zxbcdt[:, 2 * di + 2 * n:])
    padded = jnp.concatenate([jnp.zeros((w - 1, xbc.shape[1]), xbc.dtype),
                              xbc])
    conv = sum(padded[i:i + t] * m["conv_w"][i] for i in range(w))
    xbc = jax.nn.silu(conv + m["conv_b"])
    xs = xbc[:, :di].reshape(t, hp, dh)
    bm, cm = xbc[:, di:di + n], xbc[:, di + n:]
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + m["dt_bias"].astype(jnp.float32))
    out = _recurrence(xs, dt, m["A_log"], bm, cm)
    out = out + xs.astype(jnp.float32) * m["D"].astype(jnp.float32)[:, None]
    out = out.reshape(t, di).astype(y.dtype)
    out = _rmsnorm(out * jax.nn.silu(z), m["norm"]["scale"], eps)
    return _mm(out, m["out_proj"], rnd)


def _forward(params, tokens, *, cfg, rnd):
    eps = cfg["rms_norm_eps"]
    res = cfg["residual_multiplier"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    t = tokens.shape[0]
    x = params["embed"]["table"][tokens] * cfg["embedding_multiplier"]
    for i, kind in enumerate(cfg["layer_types"][:cfg["num_hidden_layers"]]):
        if kind == "mamba":
            m = params[f"blk{i}_mamba"]
            x = x + res * _mamba(m, _rmsnorm(x, m["ln"]["scale"], eps), cfg,
                                 rnd)
        else:
            a = params[f"blk{i}_attn"]
            y = _rmsnorm(x, a["ln"]["scale"], eps)
            q = _mm(y, a["wq"]["w"], rnd).reshape(t, h, hd)
            k = _mm(y, a["wk"]["w"], rnd).reshape(t, kv, hd)
            v = _mm(y, a["wv"]["w"], rnd).reshape(t, kv, hd)
            att = _attention(q, k, v, cfg["attention_multiplier"])
            x = x + res * _mm(att, a["wo"]["w"], rnd)
        f = params[f"blk{i}_mlp"]
        up = _mm(_rmsnorm(x, f["ln"]["scale"], eps), f["w_in"]["w"], rnd)
        a_, b_ = jnp.split(up, 2, axis=-1)
        x = x + res * _mm(jax.nn.silu(a_) * b_, f["w_out"]["w"], rnd)
    return _rmsnorm(x, params["head"]["ln"]["scale"], eps)


def _head(w, h, *, cfg, rnd):
    return _mm(h, w, rnd) / cfg["logits_scaling"]


def _key(cfg: dict) -> tuple:
    """The configuration's sizes as a hashable key (``layer_types`` kept
    as a tuple)."""
    out = []
    for k, v in sorted(cfg.items()):
        if isinstance(v, (int, float, str)):
            out.append((k, v))
        elif k == "layer_types":
            out.append((k, tuple(v)))
    return tuple(out)


@functools.lru_cache(maxsize=None)
def _compiled(cfg_items: tuple, int8: bool):
    """(the forward pass to the last norm, the head) jitted."""
    rnd = int8_round if int8 else (lambda a, axis=None: a)
    cfg = dict(cfg_items)
    return (jax.jit(functools.partial(_forward, cfg=cfg, rnd=rnd)),
            jax.jit(functools.partial(_head, cfg=cfg, rnd=rnd)))


def logits(params, cfg: dict, tokens, precision="float32") -> np.ndarray:
    """Next-token logits [len(tokens), vocab], f32, at every position of
    ``tokens`` (row i predicts token i + 1), as a host array: the head
    runs on ``HEAD_ROWS`` positions at a time and each block is copied
    out, since a 16k sequence's logits (6.6 GB in f32) would not fit on
    the device beside the weights and a second sequence's."""
    n = len(tokens)
    cap = cfg["max_position_embeddings"]
    if n > cap:
        raise ValueError(f"{n} tokens exceed the KV capacity {cap}")
    padded = np.zeros(cap, np.int32)
    padded[:n] = tokens
    dtype = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    trunk, head = _compiled(_key(cfg), precision == "int8")
    out = np.empty((n, cfg["vocab_size"]), np.float32)
    with jax.default_matmul_precision(
            "default" if precision == "bfloat16" else "highest"):
        h = trunk(p, jnp.asarray(padded))
        for i in range(0, n, HEAD_ROWS):
            block = head(p["head"]["out"]["w"], h[i:i + HEAD_ROWS])
            out[i:i + HEAD_ROWS] = np.asarray(block, np.float32)[:n - i]
    return out
