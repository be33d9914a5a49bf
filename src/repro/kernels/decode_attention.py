"""Pallas TPU kernel: single-token GQA decode attention over a long KV cache.

The decode-shape hot spot (``decode_32k``, ``long_500k``): one query token
attends over a KV cache of up to 524k positions.  The cache never fits VMEM,
so the kernel streams KV blocks HBM->VMEM along the innermost grid dimension
and maintains a running (flash-style) softmax in VMEM scratch:

    grid = (B, C // BLOCK_C)                    # last dim sequential on TPU

Per row b all H query heads are resident; each KV block (every KV head of
BLOCK_C positions) contributes a partial max / denominator / weighted-value
sum.  Each row's cache is picked by a slot index from scalar prefetch, so a
serving replica's decode step reads its sessions' rows straight out of its
KV slab.  The
position-validity mask (ring-buffer slots, window) is computed from the
``kpos`` sidecar, so sliding-window ring caches need no host-side compaction.

A cache of heads narrower than the 128 lanes is kept packed instead,
``[N, C, kv*hd]`` (:func:`decode_attention_packed`): a block is
``(BLOCK_C, kv*hd)``, one position a row, and each query head is widened
to ``kv*hd`` lanes, zero outside its own KV head's.

Block shape: (BLOCK_C * KV, head_dim) with BLOCK_C=512 — 512x2x128 f32 =
512 kB per K and V block, double-buffered well inside VMEM; the H x
BLOCK_C*KV logits tile computes KV times the logits a head needs (the rest
masked), which a memory-bound decode step does not feel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_C = 512


def _decode_attn_kernel(slots_ref, pos_ref, q_ref, k_ref, v_ref, kpos_ref,
                        o_ref, m_ref, l_ref, acc_ref, *, scale, window,
                        blocks, kv, g):
    b, c = pl.program_id(0), pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                       # [H, hd]
    k = k_ref[...].astype(jnp.float32)                     # [BC*kv, hd]
    v = v_ref[...].astype(jnp.float32)                     # [BC*kv, hd]
    kpos = kpos_ref[0]                                     # [1, BC*kv] int32
    pos = pos_ref[b]                                       # SMEM scalar

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale        # [H, BC*kv]
    delta = pos - kpos
    valid = (kpos >= 0) & (delta >= 0)
    if window is not None:
        valid &= delta < window
    if kv is None:
        # packed rows: each position's KV heads side by side in one row,
        # each query head zero outside its own KV head's lanes
        logits = jnp.where(valid, logits, NEG_INF)
    else:
        # cache row j holds KV head j % kv; query head i reads KV head i // g
        shape = logits.shape
        head = jax.lax.broadcasted_iota(jnp.int32, shape, 1) % kv
        group = jax.lax.broadcasted_iota(jnp.int32, shape, 0) // g
        logits = jnp.where(valid & (head == group), logits, NEG_INF)

    m_prev = m_ref[...]                                    # [H, 1]
    m_cur = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(logits - m_cur)                            # [H, BC*kv]
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(c == blocks - 1)
    def _done():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "scale", "block_c",
                                             "interpret"))
def decode_attention_packed(q, k, v, kpos, pos, window, scale, slots,
                            block_c: int = BLOCK_C, interpret: bool = False):
    """:func:`decode_attention` over packed caches: q [B,1,H,hd]; k/v
    [N,C,kv*hd] (each position's KV heads side by side, as a cache of
    heads narrower than 128 lanes is kept); kpos [N,C]; pos, slots [B]
    -> [B,1,H,hd].  The kernel reads blocks ``[block_c, kv*hd]`` of the
    rows by slot, the cache's own layout; each query head is widened to
    ``kv*hd`` lanes, zero outside its KV head's, and its output is read
    back from those lanes."""
    B, _, H, hd = q.shape
    N, C, width = k.shape
    kv = width // hd
    g = H // kv
    block_c = min(block_c, C)
    assert C % block_c == 0, f"cache len {C} % block {block_c} != 0"
    blocks = C // block_c
    own = (jnp.arange(H)[:, None] // g) == jnp.arange(kv)[None, :]  # [H,kv]
    qw = jnp.where(own[None, :, :, None], q.reshape(B, H, 1, hd), 0.0)
    kp = kpos[slots][:, None, :]                           # [B, 1, C]

    kernel = functools.partial(_decode_attn_kernel, scale=scale,
                               window=window, blocks=blocks, kv=None, g=None)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                           # slots, pos
            grid=(B, blocks),
            in_specs=[
                pl.BlockSpec((1, H, width), lambda b, c, s, p: (b, 0, 0)),
                pl.BlockSpec((None, block_c, width),
                             lambda b, c, s, p: (s[b], c, 0)),
                pl.BlockSpec((None, block_c, width),
                             lambda b, c, s, p: (s[b], c, 0)),
                pl.BlockSpec((1, 1, block_c), lambda b, c, s, p: (b, 0, c)),
            ],
            out_specs=pl.BlockSpec((1, H, width),
                                   lambda b, c, s, p: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, 1), jnp.float32),     # running max
                pltpu.VMEM((H, 1), jnp.float32),     # running denom
                pltpu.VMEM((H, width), jnp.float32),  # weighted-value acc
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, width), q.dtype),
        interpret=interpret,
    )(slots.astype(jnp.int32), pos.astype(jnp.int32),
      qw.reshape(B, H, width).astype(q.dtype), k, v, kp)
    out = jnp.where(own[None, :, :, None], out.reshape(B, H, kv, hd), 0.0)
    return out.sum(axis=2).reshape(B, 1, H, hd)


@functools.partial(jax.jit,
                   static_argnames=("window", "scale", "block_c", "interpret"))
def decode_attention(q, k, v, kpos, pos, window, scale, slots=None,
                     block_c: int = BLOCK_C, interpret: bool = False):
    """q [B,1,H,hd]; k/v [N,C,kv,hd]; kpos [N,C]; pos [B] -> [B,1,H,hd].

    Row ``b`` of the query attends over cache row ``slots[b]`` (default
    ``b``, with ``N == B``): a caller holding many sessions' caches in one
    buffer passes the whole buffer and its rows' indices, and the kernel
    streams just those rows from HBM, with nothing gathered first.

    C must be a multiple of ``block_c`` (callers pad the cache; padded slots
    carry kpos = -1 and are masked out).

    Mosaic tiles the last two dims of every block, so the kernel sees each
    cache as ``[N, C*kv, hd]``: a reshape that keeps the cache's own
    layout where its ``kv`` heads fill the layout's tile rows (f32 with 2
    KV heads of 128, for one), with each position's ``kv`` heads on
    consecutive rows.  One block of ``block_c`` positions then serves
    every query head of the row at once, the logits of other heads'
    rows masked out; ``slots`` and ``pos`` ride scalar prefetch into SMEM
    and ``kpos`` is widened to one entry per cache row.
    """
    B, _, H, hd = q.shape
    N, C, kv = k.shape[0], k.shape[1], k.shape[2]
    g = H // kv
    block_c = min(block_c, C)
    assert C % block_c == 0, f"cache len {C} % block {block_c} != 0"
    blocks = C // block_c
    if slots is None:
        slots = jnp.arange(B, dtype=jnp.int32)
    rows = block_c * kv
    kf = k.reshape(N, C * kv, hd)
    vf = v.reshape(N, C * kv, hd)
    kp = jnp.repeat(kpos[slots], kv, axis=1)[:, None, :]   # [B, 1, C*kv]

    kernel = functools.partial(_decode_attn_kernel, scale=scale,
                               window=window, blocks=blocks, kv=kv, g=g)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                           # slots, pos
            grid=(B, blocks),
            in_specs=[
                pl.BlockSpec((1, H, hd), lambda b, c, s, p: (b, 0, 0)),
                pl.BlockSpec((None, rows, hd),
                             lambda b, c, s, p: (s[b], c, 0)),
                pl.BlockSpec((None, rows, hd),
                             lambda b, c, s, p: (s[b], c, 0)),
                pl.BlockSpec((1, 1, rows), lambda b, c, s, p: (b, 0, c)),
            ],
            out_specs=pl.BlockSpec((1, H, hd), lambda b, c, s, p: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, 1), jnp.float32),     # running max
                pltpu.VMEM((H, 1), jnp.float32),     # running denom
                pltpu.VMEM((H, hd), jnp.float32),    # weighted-value acc
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        interpret=interpret,
    )(slots.astype(jnp.int32), pos.astype(jnp.int32), q.reshape(B, H, hd),
      kf, vf, kp)
    return out.reshape(B, 1, H, hd)
