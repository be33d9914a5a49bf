"""DEFER's compute-node chain as TPU pipeline parallelism.

The paper's architecture — a dispatcher feeding a chain of compute nodes that
each run a contiguous model partition and relay activations FIFO-style — maps
onto a ``shard_map`` over a *stage* mesh axis:

* compute node  ->  mesh slice along the stage axis (one stage = one node)
* TCP relay     ->  ``jax.lax.ppermute`` (circular, stage i -> i+1)
* FIFO stream   ->  microbatch scan over ``num_microbatches + S - 1`` ticks
* ZFP wire codec -> optional fixed-rate int8 block quantization of the
  relayed activation (see ``repro.kernels.block_quant``); both the int8
  payload and the f32 scale sidecar ride the same ppermute.

Semantics: tick t has stage s processing microbatch t - s (valid when
0 <= t - s < M).  Bubble ticks compute on garbage and are masked at output
collection, the standard GPipe inference schedule.  Steady-state throughput
is bounded by the slowest stage + its relay — exactly the paper's
``1 / max_i service_i`` law, with ICI taking the role of Ethernet.

The stage body is caller-supplied (``unit_fn``), so the same pipeline drives
every assigned architecture: dense/MoE/SSM units all relay ``[mb, seq, d]``
activations; hybrid relays carry the shared-attention activation the same
way (state is recomputed per stage's own layers).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels import ref as kref


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    num_stages: int
    num_microbatches: int
    axis: str = "stage"            # mesh axis the chain lives on
    compress: bool = False         # int8 block-quant the relayed activation
    quant_impl: str = "jnp"        # "jnp" (GSPMD-friendly) | "pallas"
    unroll_ticks: bool = False     # dry-run cost accounting (see dryrun.py)


# -- wire codec (the ZFP adaptation applied to the relay) -----------------------

def _wire_encode(y: jax.Array, impl: str):
    """y [mb, seq, d] -> (q int8, scales f32, shape meta is static)."""
    mb, s, d = y.shape
    flat = y.reshape(mb * s, d)
    R, C = flat.shape
    padr, padc = (-R) % kref.TILE_R, (-C) % kref.TILE_C
    if padr or padc:
        flat = jnp.pad(flat, ((0, padr), (0, padc)))
    if impl == "pallas":
        from repro.kernels import ops as kops
        q, sc, _ = kops.quantize_blocks(flat)
    else:
        q, sc = kref.quantize_blocks_ref(flat)
    return q, sc


def _wire_decode(q: jax.Array, sc: jax.Array, shape, dtype, impl: str):
    mb, s, d = shape
    if impl == "pallas":
        from repro.kernels import ops as kops
        flat = kops.dequantize_blocks(q, sc, (q.shape, q.shape[0], q.shape[1]),
                                      dtype=dtype)
    else:
        flat = kref.dequantize_blocks_ref(q, sc, dtype=dtype)
    return flat[: mb * s, :d].reshape(mb, s, d)


# -- the chain -------------------------------------------------------------------

def pipeline_apply(stage_params: Any, x_mb: Any, extra: Any = None, *,
                   unit_fn: Callable[..., Any],
                   cfg: PipelineConfig) -> Any:
    """Per-device body (run under shard_map over ``cfg.axis``).

    stage_params: local stage slice (leading dim 1, squeezed here).
    x_mb: microbatch-stream PYTREE, every leaf [M, ...] (replicated; only
    stage 0 reads it — XLA DCEs the rest after sharding propagation).  A
    plain array is the common single-activation case; enc-dec chains relay
    {"h": ..., "enc": ...} so the encoder output rides the wire as a
    pass-through activation, exactly DEFER's crossing-edge payload.
    extra: replicated pytree every stage needs whole (zamba2's weight-tied
    shared-attention block); passed as ``unit_fn(local, x, extra)``.
    Returns the same pytree with leaves [M, ...], valid on the LAST stage.
    """
    S, M = cfg.num_stages, cfg.num_microbatches
    axis = cfg.axis
    sid = jax.lax.axis_index(axis)
    tmap = jax.tree_util.tree_map
    local = tmap(lambda a: a[0], stage_params)
    perm = [(i, (i + 1) % S) for i in range(S)]

    def relay(y):
        if not cfg.compress:
            return tmap(lambda a: jax.lax.ppermute(a, axis, perm), y)

        def one(a):
            q, sc = _wire_encode(a, cfg.quant_impl)
            q = jax.lax.ppermute(q, axis, perm)
            sc = jax.lax.ppermute(sc, axis, perm)
            return _wire_decode(q, sc, a.shape, a.dtype, cfg.quant_impl)

        return tmap(one, y)

    def tick(carry, t):
        state, outbuf = carry
        minj = jnp.clip(t, 0, M - 1)
        inject = tmap(
            lambda a: jax.lax.dynamic_index_in_dim(a, minj, 0, keepdims=False),
            x_mb)
        x_in = tmap(lambda i, s: jnp.where(sid == 0, i, s), inject, state)
        y = unit_fn(local, x_in) if extra is None \
            else unit_fn(local, x_in, extra)
        # collect: last stage finished microbatch t - (S-1)
        oidx = t - (S - 1)
        take = (sid == S - 1) & (oidx >= 0)
        safe = jnp.clip(oidx, 0, M - 1)

        def collect(buf, yl):
            cur = jax.lax.dynamic_index_in_dim(buf, safe, 0, keepdims=False)
            return jax.lax.dynamic_update_index_in_dim(
                buf, jnp.where(take, yl, cur), safe, 0)

        outbuf = tmap(collect, outbuf, y)
        return (relay(y), outbuf), None

    state0 = tmap(lambda a: jnp.zeros(a.shape[1:], a.dtype), x_mb)
    out0 = tmap(jnp.zeros_like, x_mb)
    total = M + S - 1
    (_, outbuf), _ = jax.lax.scan(tick, (state0, out0), jnp.arange(total),
                                  unroll=total if cfg.unroll_ticks else 1)
    return outbuf


def make_pipeline(mesh: Mesh, cfg: PipelineConfig,
                  unit_fn: Callable[..., jax.Array],
                  data_axes: tuple[str, ...] = (),
                  with_extra: bool = False):
    """Build the sharded pipeline callable.

    Returns ``fn(stage_params, x_mb) -> y_mb`` where

    * ``stage_params``: pytree with leading dim ``num_stages`` (sharded over
      ``cfg.axis``),
    * ``x_mb [M, mb, seq, d]``: microbatch stream, batch sharded over
      ``data_axes`` (the paper's "independent chains" scale-out),
    * ``y_mb [M, mb, seq, d]``: outputs in FIFO order.

    The per-stage output buffer stays sharded over the stage axis
    ([S, M, ...]); the last-stage slice is taken outside shard_map so XLA
    moves only the finished microbatches.
    """
    pspec_w = P(cfg.axis)
    pspec_x = P(None, *data_axes)
    pspec_y = P(cfg.axis, None, *data_axes)

    tmap = jax.tree_util.tree_map

    if with_extra:
        def per_device(w, x, extra):
            out = pipeline_apply(w, x, extra, unit_fn=unit_fn, cfg=cfg)
            return tmap(lambda a: a[None], out)   # [1, M, ...] local

        sharded = jax.shard_map(per_device, mesh=mesh,
                                in_specs=(pspec_w, pspec_x, P()),
                                out_specs=pspec_y, check_vma=False)

        def fn(stage_params, x_mb, extra):
            return tmap(lambda a: a[-1], sharded(stage_params, x_mb, extra))
    else:
        def per_device(w, x):
            out = pipeline_apply(w, x, unit_fn=unit_fn, cfg=cfg)
            return tmap(lambda a: a[None], out)   # [1, M, ...] local

        sharded = jax.shard_map(per_device, mesh=mesh,
                                in_specs=(pspec_w, pspec_x),
                                out_specs=pspec_y, check_vma=False)

        def fn(stage_params, x_mb):
            # last stage's outputs
            return tmap(lambda a: a[-1], sharded(stage_params, x_mb))

    return fn


# -- stage-stacking helpers ---------------------------------------------------------

def stack_stages(unit_params: Any, n_units: int, num_stages: int):
    """[n_units, ...] unit stack -> ([S, u_per_stage, ...], valid [S, u]).

    DEFER pads the chain when layers don't divide evenly; here padded unit
    slots carry zero params and a False validity mask — ``stage_unit_fn``
    turns them into identity relays (masked residual), preserving exact
    model semantics for any (L, S).
    """
    u = -(-n_units // num_stages)              # ceil
    pad = u * num_stages - n_units

    def pad_stack(a):
        if pad:
            z = jnp.zeros((pad,) + a.shape[1:], a.dtype)
            a = jnp.concatenate([a, z], axis=0)
        return a.reshape((num_stages, u) + a.shape[1:])

    stacked = jax.tree_util.tree_map(pad_stack, unit_params)
    valid = (jnp.arange(num_stages * u) < n_units).reshape(num_stages, u)
    return stacked, valid


def make_stage_unit_fn(apply_unit: Callable[[Any, jax.Array], jax.Array]):
    """Wrap a single-unit apply into a masked multi-unit stage body.

    ``apply_unit(unit_params, x) -> y``; the stage scans its local units,
    replacing padded units with identity.
    """
    def stage_fn(stage_local, x):
        units, valid = stage_local             # units: [u, ...], valid: [u]

        def body(h, inp):
            up, ok = inp
            y = apply_unit(up, h)
            return jnp.where(ok, y, h), None

        out, _ = jax.lax.scan(body, x, (units, valid))
        return out

    return stage_fn
