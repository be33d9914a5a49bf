"""Mixture-of-Experts block: top-k router + capacity-based dispatch.

Two interchangeable dispatch implementations (numerically identical where
no tokens are dropped; tested):

* ``moe_block`` (default) — global-view scatter/gather dispatch in plain jnp;
  GSPMD infers the collectives from the expert-sharded weights.  This is the
  *baseline* path used in the 40-pair dry-run.
* ``moe_block_a2a`` — explicit per-device dispatch with ``jax.lax.all_to_all``
  under ``shard_map`` (GShard-style).  The optimized path for the hillclimb;
  see repro/distributed.py for the wrapper that binds it to a mesh.

Routing: softmax router, top-k, gates renormalized over the chosen k,
GShard dropping at capacity C = ceil(T * k / E * capacity_factor), and the
standard load-balance auxiliary loss  aux = E * sum_e f_e * p_e.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MoEConfig
from repro.models.layers import he_init, init_rmsnorm, rmsnorm


@dataclasses.dataclass(frozen=True)
class MoESpec:
    d_model: int
    d_ff: int
    gated: bool
    moe: MoEConfig


def init_moe(key, s: MoESpec, dtype) -> dict:
    ks = jax.random.split(key, 4)
    E, d, f = s.moe.num_experts, s.d_model, s.d_ff
    p = {
        "ln": init_rmsnorm(d, dtype),
        "router": he_init(ks[0], (d, E), jnp.float32),
        "up": he_init(ks[1], (E, d, f), dtype, fan_in=d),
        "down": he_init(ks[2], (E, f, d), dtype, fan_in=f),
    }
    if s.gated:
        p["gate"] = he_init(ks[3], (E, d, f), dtype, fan_in=d)
    return p


def moe_param_count(s: MoESpec) -> int:
    E, d, f = s.moe.num_experts, s.d_model, s.d_ff
    return d + d * E + (3 if s.gated else 2) * E * d * f


def _route(p, s: MoESpec, h_flat):
    """h_flat [T, d] -> (expert_idx [T,k], gates [T,k], aux_loss scalar)."""
    logits = (h_flat.astype(jnp.float32) @ p["router"])           # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, s.moe.top_k)                # [T, k]
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # load-balance aux (Switch/GShard): E * sum_e mean(frac routed) * mean(prob)
    E = s.moe.num_experts
    onehot = jax.nn.one_hot(idx[:, 0], E)                          # top-1 frac
    aux = E * jnp.mean(onehot.mean(0) * probs.mean(0)) * E
    return idx, gates.astype(h_flat.dtype), aux


def _capacity(T: int, s: MoESpec) -> int:
    c = int(np.ceil(T * s.moe.top_k / s.moe.num_experts * s.moe.capacity_factor))
    return max(4, ((c + 3) // 4) * 4)


def dispatch_indices(idx, E: int, C: int):
    """Slot positions via per-expert running count.  idx [T, k] ->
    (flat_expert [T*k], pos [T*k], keep [T*k])."""
    T, k = idx.shape
    flat = idx.reshape(T * k)
    onehot = jax.nn.one_hot(flat, E, dtype=jnp.int32)              # [T*k, E]
    pos_in_e = jnp.cumsum(onehot, axis=0) - 1                      # [T*k, E]
    pos = jnp.take_along_axis(pos_in_e, flat[:, None], axis=1)[:, 0]
    keep = pos < C
    return flat, pos, keep


def _expert_ffn(p, s: MoESpec, buf):
    """buf [E, C, d] -> [E, C, d], dense per-expert einsums (MXU-friendly)."""
    up = jnp.einsum("ecd,edf->ecf", buf, p["up"])
    if s.gated:
        up = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["gate"])) * up
    else:
        up = jax.nn.gelu(up)
    return jnp.einsum("ecf,efd->ecd", up, p["down"])


def moe_block(p: dict, s: MoESpec, x: jax.Array, eps: float = 1e-5):
    """x [B,S,d] -> ([B,S,d], aux_loss).

    Dispatch is global-view by default; with ``moe.token_shards = D`` the
    capacity buffers are built per data shard (§Perf HC2) so the scatter is
    shard-local and the cross-device exchange is an all-to-all of routed
    tokens, not an all-reduce of the whole expert buffer.
    """
    if s.moe.token_shards > 1:
        return _moe_block_sharded(p, s, x, eps, s.moe.token_shards)
    B, S, d = x.shape
    T = B * S
    h = rmsnorm(p["ln"], x, eps).reshape(T, d)
    idx, gates, aux = _route(p, s, h)
    E, k = s.moe.num_experts, s.moe.top_k
    C = _capacity(T, s)

    flat, pos, keep = dispatch_indices(idx, E, C)
    pos = jnp.where(keep, pos, C - 1)
    src = jnp.repeat(h, k, axis=0) * keep[:, None].astype(h.dtype)  # [T*k, d]
    buf = jnp.zeros((E, C, d), h.dtype).at[flat, pos].add(src)

    out_buf = _expert_ffn(p, s, buf)                                # [E, C, d]

    slots = out_buf[flat, pos] * keep[:, None].astype(h.dtype)      # [T*k, d]
    y = (slots.reshape(T, k, d) * gates[:, :, None]).sum(axis=1)
    return x + y.reshape(B, S, d), aux


def _shard_hint(t: jax.Array, spec):
    """Best-effort sharding constraint (no-op without a mesh context)."""
    try:
        from jax.sharding import PartitionSpec as P
        return jax.lax.with_sharding_constraint(t, P(*spec))
    except Exception:
        return t


def _moe_block_sharded(p: dict, s: MoESpec, x: jax.Array, eps: float,
                       D: int):
    """Per-data-shard dispatch: buf [D, E, C/D, d], scatter local to each
    shard's tokens; the (data x model) exchange of routed tokens is left to
    GSPMD as an all-to-all.  Numerically == global dispatch when no shard
    overflows its local capacity (C_l = C/D x the same capacity factor)."""
    B, S, d = x.shape
    T = B * S
    E, k = s.moe.num_experts, s.moe.top_k
    h = rmsnorm(p["ln"], x, eps).reshape(T, d)
    idx, gates, aux = _route(p, s, h)
    T_l = T // D
    C_l = _capacity(T_l, s)

    idx_s = idx.reshape(D, T_l, k)
    flat, pos, keep = jax.vmap(lambda ix: dispatch_indices(ix, E, C_l))(idx_s)
    pos = jnp.where(keep, pos, C_l - 1)
    src = jnp.repeat(h.reshape(D, T_l, d), k, axis=1) \
        * keep[..., None].astype(h.dtype)                # [D, T_l*k, d]
    buf = jnp.zeros((D, E, C_l, d), h.dtype)
    buf = _shard_hint(buf, ("data", "model", None, None))
    didx = jnp.arange(D)[:, None]
    buf = buf.at[didx, flat, pos].add(src)               # local scatter
    buf = _shard_hint(buf, ("data", "model", None, None))

    up = jnp.einsum("xecd,edf->xecf", buf, p["up"])
    if s.gated:
        up = jax.nn.silu(jnp.einsum("xecd,edf->xecf", buf, p["gate"])) * up
    else:
        up = jax.nn.gelu(up)
    out_buf = jnp.einsum("xecf,efd->xecd", up, p["down"])
    out_buf = _shard_hint(out_buf, ("data", "model", None, None))

    slots = out_buf[didx, flat, pos] * keep[..., None].astype(h.dtype)
    y = (slots.reshape(D, T_l, k, d)
         * gates.reshape(D, T_l, k)[..., None]).sum(axis=2)
    return x + y.reshape(B, S, d), aux


# -- explicit all-to-all variant (optimized path; used under shard_map) --------

def moe_block_local(p: dict, s: MoESpec, x_l: jax.Array, axis_name: str,
                    eps: float = 1e-5):
    """Per-device body for shard_map: x_l [B_l, S_l, d]; experts sharded on
    ``axis_name`` (p['up'] etc. have leading dim E_l = E / axis_size).

    dispatch locally -> all_to_all tokens to expert owners -> dense expert
    FFN on local experts -> all_to_all back -> combine.
    """
    ax = jax.lax.axis_size(axis_name)
    B_l, S_l, d = x_l.shape
    T_l = B_l * S_l
    E = s.moe.num_experts
    E_l = E // ax
    h = rmsnorm(p["ln"], x_l, eps).reshape(T_l, d)
    # router weights are replicated across the expert axis
    idx, gates, aux = _route(p, s, h)
    C = _capacity(T_l, s)

    flat, pos, keep = dispatch_indices(idx, E, C)
    pos = jnp.where(keep, pos, C - 1)
    src = jnp.repeat(h, s.moe.top_k, axis=0) * keep[:, None].astype(h.dtype)
    buf = jnp.zeros((E, C, d), h.dtype).at[flat, pos].add(src)      # [E, C, d]

    # exchange: every device sends its [E_l-slice, C] block to the owner
    buf = buf.reshape(ax, E_l, C, d)
    recv = jax.lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)                          # [ax, E_l, C, d]
    recv = jnp.moveaxis(recv, 0, 1).reshape(E_l, ax * C, d)

    out = _expert_ffn(p, s, recv)                                   # [E_l, ax*C, d]

    out = jnp.moveaxis(out.reshape(E_l, ax, C, d), 1, 0)            # [ax, E_l, C, d]
    back = jax.lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)
    back = back.reshape(E, C, d)

    slots = back[flat, pos] * keep[:, None].astype(h.dtype)
    y = (slots.reshape(T_l, s.moe.top_k, d) * gates[:, :, None]).sum(axis=1)
    return x_l + y.reshape(B_l, S_l, d), aux


def moe_flops(s: MoESpec, tokens: int) -> float:
    mats = 3 if s.gated else 2
    active = 2.0 * mats * s.d_model * s.d_ff * s.moe.top_k
    router = 2.0 * s.d_model * s.moe.num_experts
    return tokens * (active * s.moe.capacity_factor + router)
