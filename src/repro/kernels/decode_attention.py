"""Pallas TPU kernel: single-token GQA decode attention over a long KV cache.

The decode-shape hot spot (``decode_32k``, ``long_500k``): one query token
attends over a KV cache of up to 524k positions.  The cache never fits VMEM,
so the kernel streams KV blocks HBM->VMEM along the innermost grid dimension
and maintains a running (flash-style) softmax in VMEM scratch:

    grid = (B, KV_heads, C // BLOCK_C)          # last dim sequential on TPU

Per (b, kv) instance the G = H/KV query rows of that group are resident; each
KV block contributes a partial max / denominator / weighted-value sum.  The
position-validity mask (ring-buffer slots, window) is computed from the
``kpos`` sidecar, so sliding-window ring caches need no host-side compaction.

Block shape: (BLOCK_C, head_dim) with BLOCK_C=512 — 512x256 bf16 = 256 kB per
K and V block, double-buffered well inside VMEM; the G x BLOCK_C logits tile
is MXU-shaped for G in {1..32} padded to 8 sublanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_C = 512


def _decode_attn_kernel(pos_ref, q_ref, k_ref, v_ref, kpos_ref, o_ref,
                        m_ref, l_ref, acc_ref, *, scale, window, blocks):
    b, c = pl.program_id(0), pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                    # [G, hd]
    k = k_ref[0, 0].astype(jnp.float32)                    # [BC, hd]
    v = v_ref[0, 0].astype(jnp.float32)                    # [BC, hd]
    kpos = kpos_ref[0]                                     # [1, BC] int32
    pos = pos_ref[b]                                       # SMEM scalar

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale        # [G, BC]
    delta = pos - kpos
    valid = (kpos >= 0) & (delta >= 0)
    if window is not None:
        valid &= delta < window
    logits = jnp.where(valid, logits, NEG_INF)

    m_prev = m_ref[...]                                    # [G, 1]
    m_cur = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(logits - m_cur)                            # [G, BC]
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(c == blocks - 1)
    def _done():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "scale", "block_c", "interpret"))
def decode_attention(q, k, v, kpos, pos, window, scale,
                     block_c: int = BLOCK_C, interpret: bool = False):
    """q [B,1,H,hd]; k/v [B,C,kv,hd]; kpos [B,C]; pos [B] -> [B,1,H,hd].

    C must be a multiple of ``block_c`` (callers pad the cache; padded slots
    carry kpos = -1 and are masked out).

    Mosaic tiles the last two dims of every block, so the kernel sees the
    cache head-major (``[B, kv, C, hd]``: blocks of whole ``[BC, hd]``
    rows) and ``kpos`` as ``[B, 1, C]``; ``pos`` rides scalar prefetch
    into SMEM.
    """
    B, _, H, hd = q.shape
    C, kv = k.shape[1], k.shape[2]
    g = H // kv
    block_c = min(block_c, C)
    assert C % block_c == 0, f"cache len {C} % block {block_c} != 0"
    blocks = C // block_c
    qg = q.reshape(B, kv, g, hd)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(_decode_attn_kernel, scale=scale,
                               window=window, blocks=blocks)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                                   # pos
            grid=(B, kv, blocks),
            in_specs=[
                pl.BlockSpec((1, 1, g, hd), lambda b, h, c, _: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block_c, hd),
                             lambda b, h, c, _: (b, h, c, 0)),
                pl.BlockSpec((1, 1, block_c, hd),
                             lambda b, h, c, _: (b, h, c, 0)),
                pl.BlockSpec((1, 1, block_c), lambda b, h, c, _: (b, 0, c)),
            ],
            out_specs=pl.BlockSpec((1, 1, g, hd),
                                   lambda b, h, c, _: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),     # running max
                pltpu.VMEM((g, 1), jnp.float32),     # running denom
                pltpu.VMEM((g, hd), jnp.float32),    # weighted-value acc
            ]),
        out_shape=jax.ShapeDtypeStruct((B, kv, g, hd), q.dtype),
        interpret=interpret,
    )(pos.astype(jnp.int32), qg, kt, vt, kpos[:, None, :])
    return out.reshape(B, 1, H, hd)
