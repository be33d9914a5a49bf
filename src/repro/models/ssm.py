"""Mamba2 block via SSD (state-space duality), chunk-parallel form.

Recurrence per head (state S in R^{P x N}):
    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t B_t^T,    y_t = S_t C_t + D x_t

Train/prefill uses the SSD chunked algorithm (arXiv:2405.21060): quadratic
attention-like term inside chunks of length Q, linear recurrence across
chunks via ``lax.scan`` — matmul-heavy (MXU-friendly), O(S*Q) not O(S^2).
Decode is the O(1) single-step recurrence.  ``repro.kernels.ssd_scan`` is the
Pallas TPU kernel for the chunk body; this module is also its oracle
(``ssd_chunked`` with small shapes).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import SSMConfig
from repro.models.layers import he_init, init_rmsnorm, rmsnorm


@dataclasses.dataclass(frozen=True)
class MambaSpec:
    d_model: int
    ssm: SSMConfig

    @property
    def d_inner(self) -> int:
        return self.ssm.expand * self.d_model

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.ssm.head_dim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.ssm.state_dim   # x + B + C (G=1)


def init_mamba(key, s: MambaSpec, dtype) -> dict:
    ks = jax.random.split(key, 4)
    di, N, H = s.d_inner, s.ssm.state_dim, s.n_heads
    return {
        "ln": init_rmsnorm(s.d_model, dtype),
        "in_proj": he_init(ks[0], (s.d_model, 2 * di + 2 * N + H), dtype),
        "conv_w": he_init(ks[1], (s.ssm.conv_width, s.conv_channels), dtype,
                          fan_in=s.ssm.conv_width),
        "conv_b": jnp.zeros((s.conv_channels,), dtype),
        "A_log": jnp.zeros((H,), jnp.float32),          # A = -exp(A_log) ~ -1
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "norm": init_rmsnorm(di, dtype),
        "out_proj": he_init(ks[2], (di, s.d_model), dtype),
    }


def mamba_param_count(s: MambaSpec) -> int:
    di, N, H, w = s.d_inner, s.ssm.state_dim, s.n_heads, s.ssm.conv_width
    return (s.d_model                              # ln
            + s.d_model * (2 * di + 2 * N + H)     # in_proj
            + w * s.conv_channels + s.conv_channels
            + 3 * H                                # A_log, D, dt_bias
            + di                                   # gated norm
            + di * s.d_model)                      # out_proj


def _split_proj(s: MambaSpec, zxbcdt):
    di, N, H = s.d_inner, s.ssm.state_dim, s.n_heads
    z = zxbcdt[..., :di]
    xBC = zxbcdt[..., di:di + di + 2 * N]
    dt = zxbcdt[..., di + di + 2 * N:]
    return z, xBC, dt


def _causal_conv(xBC, conv_w, conv_b, conv_state=None):
    """Depthwise causal conv, width w.  xBC [B,S,ch]; conv_state [B,w-1,ch]."""
    w = conv_w.shape[0]
    if conv_state is None:
        pad = jnp.zeros_like(xBC[:, : w - 1])
    else:
        pad = conv_state
    xp = jnp.concatenate([pad, xBC], axis=1)
    out = sum(xp[:, i:i + xBC.shape[1]] * conv_w[i] for i in range(w))
    new_state = xp[:, -(w - 1):]
    return jax.nn.silu(out + conv_b), new_state


def ssd_chunked(x, dt, A, B_mat, C_mat, chunk: int, init_state=None,
                use_kernel: bool = False):
    """SSD scan.  x [B,S,H,P]; dt [B,S,H] (>0); A [H] (<0);
    B_mat/C_mat [B,S,N] (single group, broadcast over heads).
    Returns (y [B,S,H,P], final_state [B,H,P,N])."""
    Bb, S, H, P = x.shape
    N = B_mat.shape[-1]
    Q = min(chunk, S)
    S_orig = S
    if S % Q:                       # pad with dt=0 steps (state-neutral)
        pad = Q - S % Q
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_mat = jnp.pad(B_mat, ((0, 0), (0, pad), (0, 0)))
        C_mat = jnp.pad(C_mat, ((0, 0), (0, pad), (0, 0)))
        S = S + pad
    nc = S // Q

    xc = x.reshape(Bb, nc, Q, H, P)
    dtc = dt.reshape(Bb, nc, Q, H)
    Bc = B_mat.reshape(Bb, nc, Q, N)
    Cc = C_mat.reshape(Bb, nc, Q, N)

    if init_state is None:
        init_state = jnp.zeros((Bb, H, P, N), jnp.float32)

    if use_kernel:
        from repro.kernels import ops as kops
        y, final = kops.ssd_scan(xc, dtc, A, Bc, Cc, init_state)
        return y[:, :S_orig], final

    def body(state, inp):
        xq, dtq, Bq, Cq = inp          # [B,Q,H,P], [B,Q,H], [B,Q,N], [B,Q,N]
        l = dtq.astype(jnp.float32) * A                     # [B,Q,H] (<=0)
        cum = jnp.cumsum(l, axis=1)                         # [B,Q,H]
        # intra-chunk quadratic term
        Lmat = jnp.exp(cum[:, :, None, :] - cum[:, None, :, :])   # [B,Q,Q,H]
        causal = jnp.tril(jnp.ones((Q, Q), bool))
        Lmat = jnp.where(causal[None, :, :, None], Lmat, 0.0)
        CB = jnp.einsum("bqn,bsn->bqs", Cc_f(Cq), Cc_f(Bq))       # [B,Q,Q]
        scores = CB[:, :, :, None] * Lmat * dtq[:, None, :, :]    # [B,Q,Q,H]
        y = jnp.einsum("bqsh,bshp->bqhp", scores, xq.astype(jnp.float32))
        # inter-chunk: contribution of incoming state
        y += jnp.einsum("bqn,bhpn->bqhp", Cc_f(Cq), state) \
            * jnp.exp(cum)[:, :, :, None]
        # state update
        decay_to_end = jnp.exp(cum[:, -1:, :] - cum)              # [B,Q,H]
        dx = xq.astype(jnp.float32) * (dtq * decay_to_end)[..., None]
        new_state = state * jnp.exp(cum[:, -1])[:, :, None, None] \
            + jnp.einsum("bqhp,bqn->bhpn", dx, Cc_f(Bq))
        return new_state, y.astype(x.dtype)

    Cc_f = lambda t: t.astype(jnp.float32)
    final, ys = jax.lax.scan(
        body, init_state,
        (jnp.moveaxis(xc, 1, 0), jnp.moveaxis(dtc, 1, 0),
         jnp.moveaxis(Bc, 1, 0), jnp.moveaxis(Cc, 1, 0)),
    )
    y = jnp.moveaxis(ys, 0, 1).reshape(Bb, S, H, P)[:, :S_orig]
    return y, final


def mamba_mixer(p: dict, s: MambaSpec, x: jax.Array, eps: float = 1e-5,
                use_kernel: bool = False):
    """The Mamba2 mixer over a whole sequence, without the block's residual
    add (train/prefill).  x [B,S,d] -> (out [B,S,d], cache): ``cache``
    holds what a decode continues from position S, the conv's last
    ``w - 1`` inputs ``conv [B,w-1,ch]`` and the state ``ssd
    [B,H,P,N]`` f32."""
    B, S, _ = x.shape
    di, N, H, P = s.d_inner, s.ssm.state_dim, s.n_heads, s.ssm.head_dim
    h = rmsnorm(p["ln"], x, eps)
    z, xBC, dt_raw = _split_proj(s, h @ p["in_proj"])
    xBC, conv = _causal_conv(xBC, p["conv_w"], p["conv_b"])
    xs = xBC[..., :di].reshape(B, S, H, P)
    Bm = xBC[..., di:di + N]
    Cm = xBC[..., di + N:]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y, ssd = ssd_chunked(xs, dt, A, Bm, Cm, s.ssm.chunk,
                         use_kernel=use_kernel)
    y = y + xs * p["D"].astype(x.dtype)[None, None, :, None]
    y = y.reshape(B, S, di)
    y = rmsnorm(p["norm"], y * jax.nn.silu(z), eps)
    return y @ p["out_proj"], {"conv": conv, "ssd": ssd}


def mamba_block(p: dict, s: MambaSpec, x: jax.Array, eps: float = 1e-5,
                use_kernel: bool = False) -> jax.Array:
    """Full Mamba2 block (train/prefill).  x [B,S,d] -> [B,S,d]."""
    return x + mamba_mixer(p, s, x, eps, use_kernel)[0]


# -- decode -------------------------------------------------------------------

def init_mamba_cache(s: MambaSpec, batch: int, dtype) -> dict:
    return {
        "conv": jnp.zeros((batch, s.ssm.conv_width - 1, s.conv_channels), dtype),
        "ssd": jnp.zeros((batch, s.n_heads, s.ssm.head_dim, s.ssm.state_dim),
                         jnp.float32),
    }


def _step_inputs(p: dict, s: MambaSpec, x: jax.Array, conv: jax.Array,
                 eps: float):
    """One token's projections: (z, x [B,H,P], dt [B,H], A [H], B and C
    [B,N] f32, the new conv state)."""
    B = x.shape[0]
    di, N, H, P = s.d_inner, s.ssm.state_dim, s.n_heads, s.ssm.head_dim
    h = rmsnorm(p["ln"], x, eps)
    z, xBC, dt_raw = _split_proj(s, h @ p["in_proj"])
    xBC, new_conv = _causal_conv(xBC, p["conv_w"], p["conv_b"], conv)
    xs = xBC[:, 0, :di].reshape(B, H, P)
    Bm = xBC[:, 0, di:di + N].astype(jnp.float32)
    Cm = xBC[:, 0, di + N:].astype(jnp.float32)
    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + p["dt_bias"])  # [B,H]
    A = -jnp.exp(p["A_log"])
    return z, xs, dt, A, Bm, Cm, new_conv


def _ssd_update(state, xs, dt, A, Bm, Cm):
    """The recurrence for one token: (y [B,H,P] = S C, new S [B,H,P,N])."""
    a = jnp.exp(dt * A)                                            # [B,H]
    S_new = state * a[:, :, None, None] + jnp.einsum(
        "bhp,bn->bhpn", xs.astype(jnp.float32) * dt[..., None], Bm)
    return jnp.einsum("bhpn,bn->bhp", S_new, Cm), S_new


def _step_output(p: dict, s: MambaSpec, x, z, xs, y, eps: float):
    """The skip term, the gated norm and the output projection."""
    y = y + xs.astype(jnp.float32) * p["D"][None, :, None]
    y = y.reshape(x.shape[0], 1, s.d_inner).astype(x.dtype)
    y = rmsnorm(p["norm"], y * jax.nn.silu(z), eps)
    return y @ p["out_proj"]


def mamba_mixer_step(p: dict, s: MambaSpec, x: jax.Array, cache: dict,
                     eps: float = 1e-5):
    """The mixer for one token, without the residual add.  x [B,1,d] ->
    (out [B,1,d], new_cache).  O(1) in history."""
    z, xs, dt, A, Bm, Cm, conv = _step_inputs(p, s, x, cache["conv"], eps)
    y, S_new = _ssd_update(cache["ssd"], xs, dt, A, Bm, Cm)
    return _step_output(p, s, x, z, xs, y, eps), {"conv": conv, "ssd": S_new}


def mamba_decode(p: dict, s: MambaSpec, x: jax.Array, cache: dict,
                 eps: float = 1e-5):
    """One token.  x [B,1,d] -> ([B,1,d], new_cache).  O(1) in history."""
    out, cache = mamba_mixer_step(p, s, x, cache, eps)
    return x + out, cache


def mamba_mixer_slots(p: dict, s: MambaSpec, x: jax.Array, slab: dict,
                      slots: jax.Array, eps: float = 1e-5,
                      use_kernel: bool = False):
    """:func:`mamba_mixer_step` against many sessions' states held in one
    buffer: ``slab`` holds ``conv [R,w-1,ch]`` and ``ssd [R,H,P,N]``; row
    ``b`` of ``x [B,1,d]`` continues the session in slab row
    ``slots[b]``.  The conv rows move by one dynamic slice and one dynamic
    update each, in row order (rows that share a slot, such as padding,
    leave the last row's write), so XLA keeps the slab in its layout;
    with ``use_kernel`` the ``ssm_step`` kernel reads and writes the
    state rows in place by slot, else they are gathered and written back
    as the conv rows are.  Returns (out, new_slab), with the same values
    as :func:`mamba_mixer_step` on the gathered rows."""
    B = x.shape[0]
    conv, ssd = slab["conv"], slab["ssd"]
    rows = jnp.concatenate([jax.lax.dynamic_slice_in_dim(conv, slots[i], 1)
                            for i in range(B)])
    z, xs, dt, A, Bm, Cm, new_conv = _step_inputs(p, s, x, rows, eps)
    if use_kernel:
        from repro.kernels import ops as kops
        y, ssd = kops.ssm_step(ssd, slots, xs, dt, A, Bm, Cm)
    else:
        y, states = _ssd_update(ssd[slots], xs, dt, A, Bm, Cm)
        for i in range(B):
            ssd = jax.lax.dynamic_update_slice_in_dim(ssd, states[i:i + 1],
                                                      slots[i], 0)
    for i in range(B):
        conv = jax.lax.dynamic_update_slice_in_dim(conv, new_conv[i:i + 1],
                                                   slots[i], 0)
    return (_step_output(p, s, x, z, xs, y, eps),
            {"conv": conv, "ssd": ssd})


def mamba_flops(s: MambaSpec, tokens: int) -> float:
    di, N, H, P, Q = (s.d_inner, s.ssm.state_dim, s.n_heads, s.ssm.head_dim,
                      s.ssm.chunk)
    proj = 2.0 * tokens * s.d_model * (2 * di + 2 * N + H) \
        + 2.0 * tokens * di * s.d_model
    intra = 2.0 * tokens * Q * (N + H * P)       # CB^T + scores@x
    inter = 4.0 * tokens * H * P * N             # state in/out
    return proj + intra + inter
