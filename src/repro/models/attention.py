"""Attention: GQA with RoPE, chunked (memory-bounded) causal attention,
banded sliding-window attention, cross-attention, and cached decode.

Shapes: x [B, S, d]; K/V heads ``kv``; query heads ``H = g * kv``.
Caches: K,V as [B, C, kv, hd] where C = full seq for global layers or the
window size (ring buffer) for sliding-window layers.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import apply_rope, init_linear, init_rmsnorm, linear, rmsnorm

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    d_model: int
    num_heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float = 10_000.0
    window: int | None = None        # sliding window (tokens), None = global
    causal: bool = True
    q_chunk: int = 1024              # chunking for memory-bounded attention
    rope: bool = True                # False: no position embedding ("nope")
    scale: float | None = None       # softmax scale; None = 1/sqrt(head_dim)
    residual: float = 1.0            # multiplier of the block's output on
                                     # the residual stream

    @property
    def softmax_scale(self) -> float:
        return 1.0 / np.sqrt(self.head_dim) if self.scale is None \
            else self.scale


def _rope(s: AttnSpec, x, positions):
    return apply_rope(x, positions, s.rope_theta) if s.rope else x


def _residual(s: AttnSpec, x, y):
    """``x + y`` on the residual stream, ``y`` scaled by ``s.residual``."""
    return x + (y if s.residual == 1.0 else s.residual * y)


def init_attn(key, s: AttnSpec, dtype) -> dict:
    ks = jax.random.split(key, 4)
    return {
        "ln": init_rmsnorm(s.d_model, dtype),
        "wq": init_linear(ks[0], s.d_model, s.num_heads * s.head_dim, dtype),
        "wk": init_linear(ks[1], s.d_model, s.kv_heads * s.head_dim, dtype),
        "wv": init_linear(ks[2], s.d_model, s.kv_heads * s.head_dim, dtype),
        "wo": init_linear(ks[3], s.num_heads * s.head_dim, s.d_model, dtype),
    }


def _project_qkv(p, s: AttnSpec, x, positions):
    B, S, _ = x.shape
    q = linear(p["wq"], x).reshape(B, S, s.num_heads, s.head_dim)
    k = linear(p["wk"], x).reshape(B, S, s.kv_heads, s.head_dim)
    v = linear(p["wv"], x).reshape(B, S, s.kv_heads, s.head_dim)
    return _rope(s, q, positions), _rope(s, k, positions), v


def _sdpa(q, k, v, mask, scale):
    """q [B,Cq,H,hd], k/v [B,Ck,kv,hd] (GQA broadcast), mask [B?,Cq,Ck]."""
    B, Cq, H, hd = q.shape
    kv = k.shape[2]
    g = H // kv
    qg = q.reshape(B, Cq, kv, g, hd)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", qg, k).astype(jnp.float32) * scale
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, v)
    return out.reshape(B, Cq, H, hd)


def attention(p: dict, s: AttnSpec, x: jax.Array, positions: jax.Array,
              eps: float = 1e-5, kv_override=None) -> jax.Array:
    """Full-sequence attention (train / prefill), memory-bounded.

    Chunks queries with ``lax.scan`` so live logits are [B,H,Cq,S] not
    [B,H,S,S]; sliding-window layers use a banded gather so their FLOPs and
    memory scale with S * window, not S^2.
    """
    B, S, _ = x.shape
    h = rmsnorm(p["ln"], x, eps)
    q, k, v = _project_qkv(p, s, h, positions)
    scale = s.softmax_scale

    banded = s.window is not None and s.window < S
    C = min(s.q_chunk, S)
    pad = 0
    if S % C != 0:
        if banded:          # the band's key chunks need S % C == 0
            C = S
        else:               # pad the queries (their rows are dropped),
            pad = C - S % C     # so live logits stay [B,H,C,S]
    nq = (S + pad) // C
    pos_q = positions if positions.ndim == 2 else \
        jnp.broadcast_to(positions[None], (B, S))
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        pos_q = jnp.pad(pos_q, ((0, 0), (0, pad)), mode="edge")
    qs = q.reshape(B, nq, C, s.num_heads, s.head_dim)
    pos_q = pos_q.reshape(B, nq, C)

    if banded:
        out = _banded_attention(qs, k, v, pos_q, positions, s, scale, C)
    else:
        out = _chunked_attention(qs, k, v, pos_q, positions, s, scale, C)
    out = out.reshape(B, S + pad, s.num_heads * s.head_dim)[:, :S]
    return _residual(s, x, linear(p["wo"], out))


def _chunked_attention(qs, k, v, pos_q, pos_k, s, scale, C):
    """scan over query chunks; each sees the full K (causal-masked)."""
    B = qs.shape[0]
    if pos_k.ndim == 1:
        pos_k = jnp.broadcast_to(pos_k[None], (B, pos_k.shape[0]))

    def body(_, inp):
        qc, pq = inp                       # [B,C,H,hd], [B,C]
        mask = jnp.ones((B, C, pos_k.shape[1]), bool)
        if s.causal:
            mask = pq[:, :, None] >= pos_k[:, None, :]
        return None, _sdpa(qc, k, v, mask, scale)

    _, outs = jax.lax.scan(body, None,
                           (jnp.moveaxis(qs, 1, 0), jnp.moveaxis(pos_q, 1, 0)))
    return jnp.moveaxis(outs, 0, 1)        # [B,nq,C,H,hd]


def _banded_attention(qs, k, v, pos_q, pos_k, s, scale, C):
    """Sliding window: q chunk i attends only to k chunks [i-nb+1 .. i].

    nb = ceil(window/C) + 1 chunks; FLOPs ~ S * (nb*C) instead of S^2.
    """
    B, nq, _, H, hd = qs.shape
    S = k.shape[1]
    nb = int(np.ceil(s.window / C)) + 1
    kc = k.reshape(B, nq, C, s.kv_heads, hd)
    vc = v.reshape(B, nq, C, s.kv_heads, hd)
    pos_kc = (pos_k if pos_k.ndim == 2 else jnp.broadcast_to(pos_k[None], (B, S))
              ).reshape(B, nq, C)

    idx = jnp.arange(nq)[:, None] - jnp.arange(nb - 1, -1, -1)[None, :]  # [nq,nb]
    valid_chunk = idx >= 0
    idx = jnp.clip(idx, 0, nq - 1)

    def body(_, inp):
        qc, pq, band_idx, bvalid = inp
        kb = kc[:, band_idx].reshape(B, nb * C, s.kv_heads, hd)
        vb = vc[:, band_idx].reshape(B, nb * C, s.kv_heads, hd)
        pb = pos_kc[:, band_idx].reshape(B, nb * C)
        delta = pq[:, :, None] - pb[:, None, :]
        mask = (delta >= 0) & (delta < s.window)
        mask &= jnp.repeat(bvalid, C)[None, None, :]
        return None, _sdpa(qc, kb, vb, mask, scale)

    _, outs = jax.lax.scan(
        body, None,
        (jnp.moveaxis(qs, 1, 0), jnp.moveaxis(pos_q, 1, 0), idx, valid_chunk),
    )
    return jnp.moveaxis(outs, 0, 1)


# -- cross attention (enc-dec) --------------------------------------------------

def init_cross_attn(key, s: AttnSpec, dtype) -> dict:
    return init_attn(key, s, dtype)


def cross_attention(p: dict, s: AttnSpec, x: jax.Array, enc: jax.Array,
                    enc_mask: jax.Array | None = None, eps: float = 1e-5):
    B, S, _ = x.shape
    Se = enc.shape[1]
    h = rmsnorm(p["ln"], x, eps)
    q = linear(p["wq"], h).reshape(B, S, s.num_heads, s.head_dim)
    k = linear(p["wk"], enc).reshape(B, Se, s.kv_heads, s.head_dim)
    v = linear(p["wv"], enc).reshape(B, Se, s.kv_heads, s.head_dim)
    mask = jnp.ones((B, S, Se), bool) if enc_mask is None else \
        jnp.broadcast_to(enc_mask[:, None, :], (B, S, Se))
    out = _sdpa(q, k, v, mask, s.softmax_scale)
    return _residual(s, x, linear(p["wo"], out.reshape(B, S, -1)))


# -- cached decode ----------------------------------------------------------------

def init_cache(s: AttnSpec, batch: int, max_len: int, dtype,
               quant: bool = False) -> dict:
    """KV cache.  ``quant=True`` stores int8 values with one f32 scale per
    (position, kv head) row — §Perf HC5: halves cache residency and HBM
    reads per decoded token (the ZFP fixed-rate idea applied to the cache).
    """
    C = min(max_len, s.window) if s.window else max_len
    if quant:
        return {
            "k": jnp.zeros((batch, C, s.kv_heads, s.head_dim), jnp.int8),
            "v": jnp.zeros((batch, C, s.kv_heads, s.head_dim), jnp.int8),
            "kscale": jnp.zeros((batch, C, s.kv_heads), jnp.float32),
            "vscale": jnp.zeros((batch, C, s.kv_heads), jnp.float32),
        }
    return {
        "k": jnp.zeros((batch, C, s.kv_heads, s.head_dim), dtype),
        "v": jnp.zeros((batch, C, s.kv_heads, s.head_dim), dtype),
    }


def quant_rows(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x [..., hd] -> (int8 [..., hd], scale [...]) with per-row absmax."""
    absmax = jnp.abs(x.astype(jnp.float32)).max(axis=-1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def dequant_rows(q: jax.Array, scale: jax.Array, dtype=jnp.float32):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def decode_attention_ref(q, cache_k, cache_v, kpos, pos, window, scale):
    """Single-token attention over a cache. q [B,1,H,hd]; cache [B,C,kv,hd];
    kpos [B,C] absolute positions stored in each cache slot (-1 = empty)."""
    delta = pos[:, None] - kpos                         # [B,C]
    valid = (kpos >= 0) & (delta >= 0)
    if window is not None:
        valid &= delta < window
    return _sdpa(q, cache_k, cache_v, valid[:, None, :], scale)


def _decode_qkv(p: dict, s: AttnSpec, x: jax.Array, pos: jax.Array,
                eps: float):
    """The new token's rotated q [B,1,H,hd] and k, v [B,1,kv,hd]."""
    B = x.shape[0]
    h = rmsnorm(p["ln"], x, eps)
    q = linear(p["wq"], h).reshape(B, 1, s.num_heads, s.head_dim)
    k = linear(p["wk"], h).reshape(B, 1, s.kv_heads, s.head_dim)
    v = linear(p["wv"], h).reshape(B, 1, s.kv_heads, s.head_dim)
    return _rope(s, q, pos[:, None]), _rope(s, k, pos[:, None]), v


def attention_decode(p: dict, s: AttnSpec, x: jax.Array, pos: jax.Array,
                     cache: dict, kpos: jax.Array, eps: float = 1e-5,
                     use_kernel: bool = False):
    """One decode step.  x [B,1,d]; pos [B] absolute position; kpos [B,C].

    Returns (out, new_cache, new_kpos).  Sliding-window caches are ring
    buffers indexed by pos % window.
    """
    B = x.shape[0]
    q, k, v = _decode_qkv(p, s, x, pos, eps)

    C = cache["k"].shape[1]
    slot = (pos % C).astype(jnp.int32)                 # ring for window layers
    bidx = jnp.arange(B)
    nkpos = kpos.at[bidx, slot].set(pos)
    quant = cache["k"].dtype == jnp.int8
    new_cache: dict
    if quant:
        kq, ks = quant_rows(k[:, 0])
        vq, vs = quant_rows(v[:, 0])
        ck = cache["k"].at[bidx, slot].set(kq)
        cv = cache["v"].at[bidx, slot].set(vq)
        kss = cache["kscale"].at[bidx, slot].set(ks)
        vss = cache["vscale"].at[bidx, slot].set(vs)
        new_cache = {"k": ck, "v": cv, "kscale": kss, "vscale": vss}
        ck_f = dequant_rows(ck, kss, x.dtype)
        cv_f = dequant_rows(cv, vss, x.dtype)
    else:
        ck_f = ck = cache["k"].at[bidx, slot].set(k[:, 0])
        cv_f = cv = cache["v"].at[bidx, slot].set(v[:, 0])
        new_cache = {"k": ck, "v": cv}

    scale = s.softmax_scale
    if use_kernel:
        from repro.kernels import ops as kops
        out = kops.decode_attention(q, ck_f, cv_f, nkpos, pos, s.window, scale)
    else:
        out = decode_attention_ref(q, ck_f, cv_f, nkpos, pos, s.window, scale)
    out = _residual(s, x, linear(p["wo"], out.reshape(B, 1, -1)))
    return out, new_cache, nkpos


def attention_decode_slots(p: dict, s: AttnSpec, x: jax.Array,
                           pos: jax.Array, slab: dict, slots: jax.Array,
                           eps: float = 1e-5, use_kernel: bool = False):
    """One decode step against many sessions' caches held in one buffer.

    ``slab`` holds ``k``/``v`` ``[N,C,kv,hd]``, or packed as
    ``[N,C,kv*hd]`` (:func:`packed_cache`), and ``kpos`` ``[N,C]`` (f32
    caches); row ``b`` of ``x [B,1,d]`` continues the session in cache
    row ``slots[b]``.  Only each row's new position is written, one
    dynamic update per row in row order (rows that share a slot, such as
    padding, leave the last row's write): on a TPU, XLA may lay a
    scatter's operand out anew, converting the whole slab on every step,
    where a dynamic update keeps it in place.  The kernel reads the rows
    by slot; the reference path gathers them.  Returns (out, new_slab),
    with the same values as :func:`attention_decode` on the gathered
    rows.
    """
    B = x.shape[0]
    q, k, v = _decode_qkv(p, s, x, pos, eps)
    ck, cv, kp = slab["k"], slab["v"], slab["kpos"]
    packed = ck.ndim == 3
    if packed:
        k = k.reshape(B, 1, -1)
        v = v.reshape(B, 1, -1)
    at = (0,) * (ck.ndim - 2)
    slot = (pos % ck.shape[1]).astype(jnp.int32)     # ring for window layers
    for i in range(B):
        ck = jax.lax.dynamic_update_slice(ck, k[i:i + 1],
                                          (slots[i], slot[i]) + at)
        cv = jax.lax.dynamic_update_slice(cv, v[i:i + 1],
                                          (slots[i], slot[i]) + at)
        kp = jax.lax.dynamic_update_slice(kp, pos[i:i + 1, None],
                                          (slots[i], slot[i]))
    scale = s.softmax_scale
    if use_kernel:
        from repro.kernels import ops as kops
        out = kops.decode_attention(q, ck, cv, kp, pos, s.window, scale,
                                    slots)
    else:
        rows = (B, ck.shape[1], s.kv_heads, s.head_dim)
        out = decode_attention_ref(q, ck[slots].reshape(rows),
                                   cv[slots].reshape(rows), kp[slots], pos,
                                   s.window, scale)
    out = _residual(s, x, linear(p["wo"], out.reshape(B, 1, -1)))
    return out, {"k": ck, "v": cv, "kpos": kp}


def packed_cache(s: AttnSpec) -> bool:
    """Whether a decode cache holds each position's K (and V) heads as one
    ``kv * head_dim`` row, ``[B, C, kv*hd]``, rather than ``[B, C, kv,
    hd]``: where a head is narrower than the TPU's 128 lanes, the 4-D
    form would pad every head to 128 lanes (twice the memory and reads
    at 64), and the ``decode_attention`` kernel's view of it could not
    be had without a copy of the whole cache."""
    return s.head_dim < 128


def attn_flops(s: AttnSpec, tokens: int, kv_len: int) -> float:
    proj = 2.0 * tokens * s.d_model * (s.num_heads + 2 * s.kv_heads + s.num_heads) \
        * s.head_dim
    eff_kv = min(kv_len, s.window) if s.window else kv_len
    attn = 4.0 * tokens * eff_kv * s.num_heads * s.head_dim
    return proj + attn
