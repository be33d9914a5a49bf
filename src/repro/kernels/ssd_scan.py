"""Pallas TPU kernel: Mamba2 SSD (state-space dual) chunked scan.

The SSD algorithm (arXiv:2405.21060) reformulates the selective-state-space
recurrence as, per chunk of Q tokens, one quadratic *attention-like* term
(MXU matmuls) plus a rank-N running-state correction carried across chunks.
This kernel executes the per-(batch, head) scan with the chunk index as the
innermost (sequential) grid dimension and the running state held in VMEM
scratch — the HBM traffic is exactly one read of x/dt/B/C and one write of y
per token, with zero state spills:

    grid = (B, H, nc)                      # nc sequential, state persists

Per instance: xq [Q, P], dt [1, Q], Bq/Cq [Q, N], state [P, N] f32 scratch.
All four matmuls ([Q,N]x[N,Q], [Q,Q]x[Q,P], [Q,N]x[N,P], [P,Q]x[Q,N]) are
MXU-shaped for Q in {128, 256}, N/P in {64, 128}.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, init_ref,
                y_ref, fin_ref, state_ref, *, nc):
    h, ci = pl.program_id(1), pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = init_ref[0, 0].astype(jnp.float32)

    xq = x_ref[0, 0, 0].astype(jnp.float32)                # [Q, P]
    dt_row = dt_ref[0, 0, 0].astype(jnp.float32)           # [1, Q]
    A = a_ref[h]                                           # SMEM scalar (<0)
    Bq = b_ref[0, 0].astype(jnp.float32)                   # [Q, N]
    Cq = c_ref[0, 0].astype(jnp.float32)                   # [Q, N]
    Q = xq.shape[0]

    # Column and row views of dt and its running sum, built with masked
    # reductions over a [Q, Q] grid: VPU adds in f32, no cumsum primitive
    # and no vector transpose (neither lowers to Mosaic).
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = col <= row
    l_row = dt_row * A                                     # [1, Q] <= 0
    dt_col = jnp.sum(jnp.where(row == col, dt_row, 0.0), axis=1,
                     keepdims=True)                        # [Q, 1]
    l_col = dt_col * A
    cum_col = jnp.sum(jnp.where(causal, l_row, 0.0), axis=1,
                      keepdims=True)                       # [Q, 1]
    cum_row = jnp.sum(jnp.where(row <= col, l_col, 0.0), axis=0,
                      keepdims=True)                       # [1, Q]
    cum_end = jnp.sum(l_row, axis=1, keepdims=True)        # [1, 1]

    # intra-chunk attention-like term
    Lmat = jnp.where(causal, jnp.exp(cum_col - cum_row), 0.0)   # [Q, Q]
    CB = jax.lax.dot_general(Cq, Bq, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    scores = CB * Lmat * dt_row                            # [Q, Q]
    y = jax.lax.dot_general(scores, xq, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # inter-chunk: incoming state contribution  C_t state^T * exp(cum_t)
    state = state_ref[...]                                 # [P, N]
    y += jax.lax.dot_general(Cq, state, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) \
        * jnp.exp(cum_col)
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    # state update: decay + sum_t dt_t decay_t x_t B_t^T
    decay_to_end = jnp.exp(cum_end - cum_col)              # [Q, 1]
    dx = xq * (dt_col * decay_to_end)                      # [Q, P]
    state_ref[...] = state * jnp.exp(cum_end) + jax.lax.dot_general(
        dx, Bq, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ci == nc - 1)
    def _done():
        fin_ref[0, 0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_scan(xc, dtc, A, Bc, Cc, init_state, interpret: bool = False):
    """xc [B,nc,Q,H,P]; dtc [B,nc,Q,H]; A [H]; Bc/Cc [B,nc,Q,N];
    init_state [B,H,P,N] f32 -> (y [B,nc,Q,H,P], final_state [B,H,P,N]).

    Mosaic tiles the last two dims of every block, so the kernel sees x
    and y head-major (``[B, H, nc, Q, P]``) and dt as rows
    (``[B, H, nc, 1, Q]``); A rides scalar prefetch into SMEM.
    """
    B, nc, Q, H, P = xc.shape
    N = Bc.shape[-1]
    xt = xc.transpose(0, 3, 1, 2, 4)
    dtt = dtc.transpose(0, 3, 1, 2)[:, :, :, None, :]
    kernel = functools.partial(_ssd_kernel, nc=nc)

    def chunk(b, h, c, _):          # x, dt and y: one (b, h, chunk) block
        return b, h, c, 0, 0

    y, fin = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                                   # A
            grid=(B, H, nc),
            in_specs=[
                pl.BlockSpec((1, 1, 1, Q, P), chunk),
                pl.BlockSpec((1, 1, 1, 1, Q), chunk),
                pl.BlockSpec((1, 1, Q, N), lambda b, h, c, _: (b, c, 0, 0)),
                pl.BlockSpec((1, 1, Q, N), lambda b, h, c, _: (b, c, 0, 0)),
                pl.BlockSpec((1, 1, P, N), lambda b, h, c, _: (b, h, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, 1, Q, P), chunk),
                pl.BlockSpec((1, 1, P, N), lambda b, h, c, _: (b, h, 0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)]),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nc, Q, P), xc.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        interpret=interpret,
    )(A.astype(jnp.float32), xt, dtt, Bc, Cc, init_state.astype(jnp.float32))
    return y.transpose(0, 2, 3, 1, 4), fin
