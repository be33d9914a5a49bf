"""Weights and keys from ``--seed``, made on the device in one jitted call.

Every parameter leaf of every node gets a key folded from the node's name
by ``zlib.crc32`` (stable across processes, unlike ``hash``) and the
leaf's index in its node, so one seed gives the same weights in every run.

Leaves are scaled by their role so that every layer matters to the output:
a matrix or kernel (rank >= 2) is drawn with variance 1 / fan-in, where the
fan-in is the product of all but its last axis (``[in, out]`` matrices and
``HWIO`` convolution kernels alike); a vector named ``scale`` (a norm's or
a folded batch norm's gain) is ``1 + 0.02 N``; any other vector (a bias)
is ``0.02 N``.  An embedding table is a matrix like any other: the norm
that follows it removes its scale.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

GAIN_NOISE = 0.02     # spread of norm gains around 1, and of biases around 0


def seed_key(seed: int) -> jax.Array:
    """A PRNG key that keeps every bit of a seed of up to 64 bits
    (``jax.random.PRNGKey`` alone drops the high word without x64)."""
    seed = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def name_key(key: jax.Array, name: str) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _leaf(key, path, spec):
    shape, dtype = tuple(spec.shape), spec.dtype
    if not jnp.issubdtype(dtype, jnp.floating):
        return jnp.zeros(shape, dtype)
    z = jax.random.normal(key, shape, jnp.float32)
    if len(shape) >= 2:
        z = z / np.sqrt(float(np.prod(shape[:-1])))
    elif path and getattr(path[-1], "key", None) == "scale":
        z = 1.0 + GAIN_NOISE * z
    else:
        z = GAIN_NOISE * z
    return z.astype(dtype)


def param_specs(graph) -> dict:
    """``{node name: param_spec}`` of every node (``{}`` where it has no
    parameters: the dispatcher ships an entry for each)."""
    return {n.name: n.param_spec for n in graph.nodes}


def init_params(specs: dict, seed: int) -> dict:
    """All parameters of ``specs`` (``{node name: pytree of
    ShapeDtypeStruct}``) in one jitted call on the default device."""

    def init(key):
        out = {}
        for name, spec in specs.items():
            k = name_key(key, name)
            flat, treedef = jax.tree_util.tree_flatten_with_path(spec)
            leaves = [_leaf(jax.random.fold_in(k, i), path, leaf)
                      for i, (path, leaf) in enumerate(flat)]
            out[name] = jax.tree_util.tree_unflatten(treedef, leaves)
        return out

    return jax.jit(init)(seed_key(seed))
