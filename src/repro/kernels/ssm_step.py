"""Pallas TPU kernel: one Mamba2 decode step on many sessions' states held
in one buffer (a serving replica's slab), read and written in place.

Per row ``b`` of a decode wave and per head ``h`` (state ``S`` in
``R^{P x N}``, one group: ``B_t`` and ``C_t`` shared by every head):

    S <- exp(dt_h A_h) S + dt_h x_h B_t^T,        y_h = S C_t

The state of row ``b`` lives in slab row ``slots[b]``; ``slots`` rides
scalar prefetch and picks each block by slot, and the slab is aliased to
the new-state output, so a step reads and writes only the wave's rows and
nothing gathers them first.  The slab's leaf ``[R, H, P, N]`` is seen as
``[R, H*P, N]`` (a free reshape: ``P`` fills whole tiles of sublanes), and
the grid walks ``(row, block of heads)``:

    grid = (B, H*P // M)          # M = (heads per block) * P rows

Per instance: the state block ``[M, N]`` f32, the rows ``[1, M]`` of the
decay ``exp(dt A)`` and of ``dt x`` (expanded over ``P``), and ``B_t`` /
``C_t`` as ``[1, N]``.  The decay must scale each state row and ``dt x``
must scale ``B_t`` per row, so both are turned from lane-dense rows into
one value per row by an outer product on the MXU (a row against a row,
contracting a zero-padded axis of 8), as is the readout ``C_t S^T``; all
three at ``HIGHEST`` precision, so the step is f32 arithmetic.  The kernel
is memory bound: each row moves its state once in and once out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ROWS = 1024           # state rows (heads x P) per block: 512 KiB f32
PAD = 8                     # the contracted axis of the outer products
_HI = jax.lax.Precision.HIGHEST


def _rows8(v):
    """A ``[1, L]`` row as the first row of an otherwise zero ``[8, L]``."""
    first = jax.lax.broadcasted_iota(jnp.int32, (PAD, v.shape[1]), 0) == 0
    return jnp.where(first, jnp.broadcast_to(v, (PAD, v.shape[1])), 0.0)


def _outer(u8, v8):
    """``u^T v`` of two zero-padded rows: ``[8, M] x [8, N] -> [M, N]``."""
    return jax.lax.dot_general(u8, v8, (((0,), (0,)), ((), ())),
                               precision=_HI,
                               preferred_element_type=jnp.float32)


def _ssm_step_kernel(slots_ref, a_ref, dx_ref, b_ref, c_ref, s_ref,
                     y_ref, o_ref):
    del slots_ref                       # used by the index maps only
    n = b_ref.shape[-1]
    ones = _rows8(jnp.ones((1, n), jnp.float32))
    decay = _outer(_rows8(a_ref[0]), ones)                    # [M, N]
    update = _outer(_rows8(dx_ref[0]), _rows8(b_ref[0]))      # [M, N]
    s = s_ref[...] * decay + update
    o_ref[...] = s
    y = jax.lax.dot_general(_rows8(c_ref[0]), s, (((1,), (1,)), ((), ())),
                            precision=_HI,
                            preferred_element_type=jnp.float32)   # [8, M]
    y_ref[0] = y[0:1]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_step(state, slots, x, dt, A, B, C, interpret: bool = False):
    """state [R,H,P,N] f32 (the slab, aliased to the new state); slots
    [Bw] int32; x [Bw,H,P]; dt [Bw,H] (> 0); A [H] (< 0); B, C [Bw,N] ->
    (y [Bw,H,P] f32 = the new state read out by C, new state [R,H,P,N]).

    Rows that share a slot (padding rows on the scratch row) are computed
    in row order, the last one's state kept.  The skip term ``D x`` is the
    caller's."""
    R, H, P, N = state.shape
    Bw = x.shape[0]
    hp = H * P
    m = min(BLOCK_ROWS, hp)
    assert hp % m == 0 and m % P == 0, (H, P, m)
    dt = dt.astype(jnp.float32)
    a = jnp.repeat(jnp.exp(dt * A.astype(jnp.float32)), P, axis=1)
    dx = (x.astype(jnp.float32) * dt[..., None]).reshape(Bw, 1, hp)
    row = lambda b, j, s: (b, 0, j)
    vec = lambda b, j, s: (b, 0, 0)
    slab = lambda b, j, s: (s[b], j, 0)
    y, new = pl.pallas_call(
        _ssm_step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                              # slots
            grid=(Bw, hp // m),
            in_specs=[
                pl.BlockSpec((1, 1, m), row),                   # decay
                pl.BlockSpec((1, 1, m), row),                   # dt x
                pl.BlockSpec((1, 1, N), vec),                   # B
                pl.BlockSpec((1, 1, N), vec),                   # C
                pl.BlockSpec((None, m, N), slab),               # state
            ],
            out_specs=[
                pl.BlockSpec((1, 1, m), row),                   # y
                pl.BlockSpec((None, m, N), slab),               # new state
            ]),
        out_shape=[jax.ShapeDtypeStruct((Bw, 1, hp), jnp.float32),
                   jax.ShapeDtypeStruct((R, hp, N), jnp.float32)],
        # operand 5 (slots counted) is the state, aliased to output 1
        input_output_aliases={5: 1},
        interpret=interpret,
    )(slots.astype(jnp.int32), a.reshape(Bw, 1, hp), dx,
      B.astype(jnp.float32).reshape(Bw, 1, N),
      C.astype(jnp.float32).reshape(Bw, 1, N),
      state.reshape(R, hp, N))
    return y.reshape(Bw, H, P), new.reshape(R, H, P, N)
