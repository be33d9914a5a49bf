"""Pallas TPU kernel: fixed-rate shared-scale int8 block quantization.

This is the TPU-native adaptation of DEFER's ZFP wire codec (DESIGN.md §4):
ZFP's core idea — *fixed-rate blockwise compression of floats* — maps onto
per-(8,128)-VREG-tile shared-scale int8 quantization executed in VMEM.  The
pipeline runtime quantizes an inter-stage activation before ``ppermute`` and
dequantizes after, cutting ICI bytes 2x (bf16) / 4x (f32) plus a 1/1024
scale sidecar, with a fixed (rate-determined) error envelope exactly like ZFP.

Tiling: the (8, 128) tile is the native VREG shape (8 sublanes x 128 lanes),
so absmax-reduction and the scale broadcast stay register-local; full-width
strips of ``BLOCK_VALUES`` values are staged through VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

TILE_R, TILE_C = 8, 128

# VMEM block: a full-width strip of about 512x512 values (1 MB of f32 in,
# 0.25 MB int8 out), comfortably inside VMEM with double buffering.  The
# strip spans every column so the scale block [rows/8, C/128] is whole
# along its lane dim, and its row count is a multiple of 64 (or all of R)
# so the scale block's rows are a multiple of 8: Mosaic's tiling rule for
# the last two block dims.
BLOCK_VALUES = 512 * 512


def _block_rows(R: int, C: int) -> int:
    rows = max(64, BLOCK_VALUES // C // 64 * 64)
    return R if rows >= R else rows


def _quant_kernel(x_ref, q_ref, s_ref):
    """x strip [rows, C] -> int8 strip + scales [rows/8, C/128], one
    (8, 128) tile column at a time."""
    rows, cols = x_ref.shape
    for j in range(cols // TILE_C):
        lanes = pl.ds(j * TILE_C, TILE_C)
        xt = x_ref[:, lanes].astype(jnp.float32).reshape(
            rows // TILE_R, TILE_R, TILE_C)
        absmax = jnp.abs(xt).max(axis=2).max(axis=1, keepdims=True)
        scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)     # [rows/8, 1]
        q = jnp.clip(jnp.round(xt / scale[:, :, None]), -127.0, 127.0)
        q_ref[:, lanes] = q.reshape(rows, TILE_C).astype(jnp.int8)
        s_ref[:, pl.ds(j, 1)] = scale


def _dequant_kernel(q_ref, s_ref, x_ref):
    rows, cols = q_ref.shape
    for j in range(cols // TILE_C):
        lanes = pl.ds(j * TILE_C, TILE_C)
        qt = q_ref[:, lanes].astype(jnp.float32).reshape(
            rows // TILE_R, TILE_R, TILE_C)
        x = qt * s_ref[:, pl.ds(j, 1)][:, :, None]
        x_ref[:, lanes] = x.reshape(rows, TILE_C).astype(x_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_blocks(x: jax.Array, interpret: bool = False):
    """x [R, C] (R % 8 == 0, C % 128 == 0) -> (q int8 [R,C], scales [R/8, C/128])."""
    R, C = x.shape
    rows = _block_rows(R, C)
    return pl.pallas_call(
        _quant_kernel,
        grid=(pl.cdiv(R, rows),),
        in_specs=[pl.BlockSpec((rows, C), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((rows, C), lambda i: (i, 0)),
            pl.BlockSpec((rows // TILE_R, C // TILE_C), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, C), jnp.int8),
            jax.ShapeDtypeStruct((R // TILE_R, C // TILE_C), jnp.float32),
        ],
        interpret=interpret,
    )(x)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def dequantize_blocks(q: jax.Array, scales: jax.Array, dtype=jnp.float32,
                      interpret: bool = False):
    R, C = q.shape
    rows = _block_rows(R, C)
    return pl.pallas_call(
        _dequant_kernel,
        grid=(pl.cdiv(R, rows),),
        in_specs=[
            pl.BlockSpec((rows, C), lambda i: (i, 0)),
            pl.BlockSpec((rows // TILE_R, C // TILE_C), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), dtype),
        interpret=interpret,
    )(q, scales)


# -- host-facing wire entry points (the serving runtime's "q8" serializer) ---
#
# The kernels above want an aligned 2D [R, C] grid; the wire sees arbitrary
# activation pytree leaves.  These wrappers flatten, zero-pad to a whole
# number of (8, 128) tiles, and run the kernel compiled on a TPU or in
# interpret mode on the CPU (same numerics, still one jitted call).

WIRE_C = TILE_C


def _pow2_tiles(n: int) -> int:
    """Whole (8, 128) tiles covering n values, rounded up to a power of two
    so the jit cache sees a bounded set of [R, 128] shapes regardless of
    ragged batch sizes (one specialization per doubling, not per size)."""
    tiles = -(-n // (TILE_R * WIRE_C))
    p = 1
    while p < tiles:
        p *= 2
    return p


def quantize_wire(arr: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Arbitrary-shape array -> (int8 payload [Np], float32 scales [Np/1024]).

    ``Np`` is ``arr.size`` zero-padded up to a power-of-two count of
    (8, 128) tiles; the caller records the true element count and may trim
    the int8 payload to it (zero input quantizes to zero, so the padding is
    reconstructible on decode).
    """
    a = np.ascontiguousarray(arr, dtype=np.float32).ravel()
    n = a.size
    if n == 0:
        return np.zeros(0, np.int8), np.zeros(0, np.float32)
    np_full = _pow2_tiles(n) * TILE_R * WIRE_C
    if np_full > n:
        a = np.concatenate([a, np.zeros(np_full - n, np.float32)])
    x = a.reshape(-1, WIRE_C)
    q, s = quantize_blocks(jnp.asarray(x), interpret=interpret_mode())
    return np.asarray(q).ravel(), np.asarray(s, np.float32).ravel()


def dequantize_wire(q: np.ndarray, scales: np.ndarray, n: int,
                    shape: tuple[int, ...], dtype) -> np.ndarray:
    """Invert :func:`quantize_wire` back to ``shape``/``dtype``.  Accepts an
    int8 payload trimmed to ``n`` — the tail tiles quantized from zero
    padding are re-synthesized as zeros."""
    if n == 0:
        return np.zeros(shape, dtype)
    np_full = scales.size * TILE_R * TILE_C    # one scale per (8, 128) tile
    qf = np.zeros(np_full, np.int8)
    qf[:q.size] = q
    q2 = qf.reshape(-1, WIRE_C)
    s2 = np.ascontiguousarray(scales, dtype=np.float32).reshape(
        -1, WIRE_C // TILE_C)
    out = dequantize_blocks(jnp.asarray(q2), jnp.asarray(s2),
                            interpret=interpret_mode())
    return np.asarray(out).ravel()[:n].reshape(shape).astype(dtype, copy=False)
