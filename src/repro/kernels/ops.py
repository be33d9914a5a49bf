"""Public jit'd wrappers around the Pallas kernels.

On the CPU every wrapper runs the kernel in ``interpret=True`` mode — the
kernel body executes in Python for bit-faithful validation; on a TPU the
same calls lower to Mosaic (see :func:`repro.kernels.interpret_mode`).
Padding/reshaping to tile multiples lives here so kernel bodies stay
shape-exact.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import block_quant as _bq
from repro.kernels import decode_attention as _da
from repro.kernels import interpret_mode
from repro.kernels import ssd_scan as _ssd


# -- block quantization (wire compression for the DEFER pipeline) ---------------

def quantize_blocks(x: jax.Array):
    """Any-rank x -> (q int8 [R,C], scales, meta) with padding to (8,128)."""
    shape = x.shape
    flat = x.reshape(-1, shape[-1]) if x.ndim > 1 else x.reshape(1, -1)
    R, C = flat.shape
    padr, padc = (-R) % _bq.TILE_R, (-C) % _bq.TILE_C
    if padr or padc:
        flat = jnp.pad(flat, ((0, padr), (0, padc)))
    q, s = _bq.quantize_blocks(flat, interpret=interpret_mode())
    return q, s, (shape, R, C)


def dequantize_blocks(q: jax.Array, scales: jax.Array, meta, dtype=jnp.float32):
    shape, R, C = meta
    x = _bq.dequantize_blocks(q, scales, dtype=dtype,
                              interpret=interpret_mode())
    return x[:R, :C].reshape(shape)


def quant_bytes(shape, dtype=jnp.bfloat16) -> tuple[int, int]:
    """(raw_bytes, wire_bytes) for a tensor sent through the quant codec."""
    n = int(np.prod(shape))
    raw = n * jnp.dtype(dtype).itemsize
    wire = n * 1 + (n // (_bq.TILE_R * _bq.TILE_C)) * 4   # int8 + f32 scales
    return raw, wire


# -- decode attention ------------------------------------------------------------

def decode_attention(q, k, v, kpos, pos, window, scale):
    """q [B,1,H,hd]; k/v [B,C,kv,hd]; kpos [B,C]; pos [B] -> [B,1,H,hd]."""
    C = k.shape[1]
    block = min(_da.BLOCK_C, C)
    pad = (-C) % block
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kpos = jnp.pad(kpos, ((0, 0), (0, pad)), constant_values=-1)
    return _da.decode_attention(q, k, v, kpos, pos, window, scale,
                                block_c=block, interpret=interpret_mode())


# -- SSD scan ----------------------------------------------------------------------

def ssd_scan(xc, dtc, A, Bc, Cc, init_state):
    """Chunked inputs -> (y [B, nc*Q, H, P], final_state [B,H,P,N]).

    Matches the return convention of ``ssm.ssd_chunked``'s scan path: callers
    trim padding rows themselves (they know S_orig).
    """
    B, nc, Q, H, P = xc.shape
    y, fin = _ssd.ssd_scan(xc, dtc, A, Bc, Cc, init_state,
                           interpret=interpret_mode())
    return y.reshape(B, nc * Q, H, P), fin
