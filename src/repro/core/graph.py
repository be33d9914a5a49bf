"""Layer-graph IR — the JAX analogue of the Keras layer DAG that DEFER traverses.

DEFER partitions a model by walking its layer DAG and cutting it into
contiguous sub-networks.  We represent any model (CNN or transformer) as a
:class:`LayerGraph` of :class:`LayerNode`s.  Each node carries

* ``fn``        — a pure function ``(params, *inputs) -> output`` (JAX),
* ``param_spec``— pytree of ShapeDtypeStructs for its parameters,
* cost terms    — FLOPs, parameter bytes, and output-activation bytes,

so the partitioner can cost a cut without running anything, exactly like the
paper's dispatcher plans partitions before shipping them.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Params = Any  # pytree


def tree_bytes(tree: Any) -> int:
    """Total bytes of every leaf (works for arrays and ShapeDtypeStructs)."""
    leaves = jax.tree_util.tree_leaves(tree)
    total = 0
    for leaf in leaves:
        size = int(np.prod(leaf.shape)) if leaf.shape else 1
        total += size * jnp.dtype(leaf.dtype).itemsize
    return total


def tree_params(tree: Any) -> int:
    leaves = jax.tree_util.tree_leaves(tree)
    return sum(int(np.prod(l.shape)) if l.shape else 1 for l in leaves)


@dataclasses.dataclass
class LayerDecode:
    """Autoregressive view of a stateful layer (attention with a KV cache,
    or a recurrent layer with a fixed-size state).

    ``prefill_fn(params, x)`` runs the layer over a full prompt
    ``[B, S, ...]`` and returns ``(y, cache)`` — the cache pytree holds
    everything the layer needs to continue from position ``S`` (e.g.
    K/V buffers of fixed capacity plus a slot-position vector), with a
    leading batch axis: a session's caches (``B=1``) become one row of
    the serving replica's KV slab.  ``step_fn(params, cache, x, pos)``
    consumes ONE new token per row (``x: [B, 1, ...]``, ``pos: [B]
    int32`` — rows may sit at *different* sequence positions) and returns
    ``(y, new_cache)``.  It takes its cache in two forms: a batch of
    caches (the single-device reference), and a :class:`SlabRows` — the
    serving replica's whole slab and the wave's slots — from which it
    reads each row's cache in place, writing only what the step changes
    and returning ``(y, SlabRows)`` with the new slab; the values are the
    same either way.  Both functions must be jit-traceable; cache leaves
    must keep a fixed shape so the slab's rows are the same for every
    prompt and a decode wave specializes once per batch size.
    """

    prefill_fn: Callable[..., Any]         # (params, x) -> (y, cache)
    step_fn: Callable[..., Any]            # (params, cache, x, pos) -> (y, new_cache)
    # True for a fixed-size recurrent state that every step reads and
    # writes whole (Mamba2's conv and SSD state), False for a KV cache a
    # step appends one position to; the serving replica counts the two
    # kinds' bytes apart
    recurrent: bool = False


class SlabRows(NamedTuple):
    """A decode wave's rows of a serving replica's KV slab: one layer's
    slab pytree (every leaf ``[capacity + 1, ...]``) and ``slots`` (int32
    ``[B]``), the slab row each of the wave's rows continues."""

    slab: Any
    slots: Any


@dataclasses.dataclass
class LayerNode:
    """One layer (or fused block) in the model DAG."""

    name: str
    fn: Callable[..., Any]                 # (params, *inputs) -> output
    param_spec: Any                        # pytree of ShapeDtypeStruct
    inputs: Sequence[str]                  # names of producer nodes ('' = graph input)
    out_spec: jax.ShapeDtypeStruct         # activation this node emits
    flops: float                           # fwd FLOPs for one sample batch
    meta: Mapping[str, Any] = dataclasses.field(default_factory=dict)
    # True iff this layer preserves its middle axes and acts independently
    # along them (token-wise MLP/FFN, norms, elementwise): the runtime may
    # then zero-pad those axes to merge near-miss shapes into one batch
    # bucket.  Layers that mix positions (attention over the padded axis,
    # pooling with edge effects) must set False — a serving segment
    # containing any pad-unsafe layer falls back to exact bucketing.
    pad_safe: bool = True
    # stateful autoregressive view; None for stateless layers, whose ``fn``
    # already works one token at a time (embeddings, norms, token-wise MLP)
    decode: LayerDecode | None = None

    @property
    def param_bytes(self) -> int:
        return tree_bytes(self.param_spec)

    @property
    def out_bytes(self) -> int:
        return tree_bytes(self.out_spec)


class LayerGraph:
    """A topologically-ordered DAG of layers plus init/apply utilities.

    Mirrors the role of the Keras model object in DEFER: it can be traversed,
    cut into contiguous partitions, and each partition materialized as a
    standalone callable (the "new model of just the partitioned layers").
    """

    def __init__(self, name: str, input_spec: jax.ShapeDtypeStruct):
        self.name = name
        self.input_spec = input_spec
        self.nodes: list[LayerNode] = []
        self._by_name: dict[str, LayerNode] = {}

    # -- construction -----------------------------------------------------
    def add(self, node: LayerNode) -> str:
        if node.name in self._by_name:
            raise ValueError(f"duplicate layer name {node.name!r}")
        for inp in node.inputs:
            if inp and inp not in self._by_name:
                raise ValueError(
                    f"layer {node.name!r} consumes unknown producer {inp!r}"
                )
        self.nodes.append(node)
        self._by_name[node.name] = node
        return node.name

    def layer(self, name: str, fn, param_spec, inputs, out_spec, flops,
              pad_safe: bool = True, decode: LayerDecode | None = None,
              **meta):
        return self.add(
            LayerNode(name, fn, param_spec, tuple(inputs), out_spec, flops,
                      meta, pad_safe=pad_safe, decode=decode)
        )

    @property
    def decode_capable(self) -> bool:
        """True iff the graph declares an autoregressive view: at least one
        stateful :class:`LayerDecode` node AND a pure chain shape (every
        node consumes exactly one producer), so any contiguous partition
        has a single boundary activation for token-step frames to carry."""
        return (any(n.decode is not None for n in self.nodes)
                and all(len(n.inputs) == 1 for n in self.nodes))

    def __len__(self) -> int:
        return len(self.nodes)

    def __getitem__(self, name: str) -> LayerNode:
        return self._by_name[name]

    # -- aggregate costs ---------------------------------------------------
    @property
    def total_flops(self) -> float:
        return sum(n.flops for n in self.nodes)

    @property
    def total_param_bytes(self) -> int:
        return sum(n.param_bytes for n in self.nodes)

    # -- cut legality -------------------------------------------------------
    def cut_cost(self, i: int) -> int:
        """Bytes crossing a cut placed after node index ``i``.

        A cut is the wire between two DEFER compute nodes: every edge from a
        producer at index <= i to a consumer at index > i crosses it.  The
        transferred payload is the union of crossing producer activations
        (each is sent once, the receiving partition fans it out locally).
        """
        total = 0
        for name in self.crossing_names(i):
            total += (
                tree_bytes(self.input_spec)
                if name == ""
                else self._by_name[name].out_bytes
            )
        return total

    def crossing_names(self, i: int) -> list[str]:
        """Activations crossing a cut placed after node index ``i``.

        Every edge from a producer at index <= i (or the graph input '') to
        a consumer at index > i crosses the cut.  Each crossing activation
        is sent once; activations produced before an intermediate stage and
        consumed after it pass through that stage's wire too (the chain has
        no other path).
        """
        consumed_after = {inp for n in self.nodes[i + 1:] for inp in n.inputs}
        names = [n.name for n in self.nodes[: i + 1] if n.name in consumed_after]
        if "" in consumed_after:
            names.insert(0, "")
        return names

    # -- init / apply --------------------------------------------------------
    def init(self, key: jax.Array, scale: float = 0.02) -> Params:
        """Materialize real parameters for every node (normal init)."""
        params: dict[str, Any] = {}
        for node in self.nodes:
            leaves, treedef = jax.tree_util.tree_flatten(node.param_spec)
            keys = jax.random.split(jax.random.fold_in(key, hash(node.name) % (2**31)),
                                    max(1, len(leaves)))
            mats = []
            for k, leaf in zip(keys, leaves):
                if jnp.issubdtype(leaf.dtype, jnp.floating):
                    mats.append(
                        (jax.random.normal(k, leaf.shape, jnp.float32) * scale
                         ).astype(leaf.dtype)
                    )
                else:
                    mats.append(jnp.zeros(leaf.shape, leaf.dtype))
            params[node.name] = jax.tree_util.tree_unflatten(treedef, mats)
        return params

    def apply(self, params: Params, x: jax.Array,
              nodes: Sequence[LayerNode] | None = None,
              boundary_inputs: Mapping[str, jax.Array] | None = None) -> jax.Array:
        """Run (a slice of) the graph.

        ``boundary_inputs`` supplies activations produced by an earlier
        partition — this is exactly what a DEFER compute node receives on its
        incoming socket.
        """
        nodes = list(self.nodes) if nodes is None else list(nodes)
        acts: dict[str, jax.Array] = {"": x}
        if boundary_inputs:
            acts.update(boundary_inputs)
        out = x
        for node in nodes:
            args = [acts[i] for i in node.inputs]
            out = node.fn(params[node.name], *args)
            acts[node.name] = out
        return out

    # -- partition materialization -------------------------------------------
    def slice_nodes(self, lo: int, hi: int) -> list[LayerNode]:
        """Nodes of partition [lo, hi) in topological order."""
        return self.nodes[lo:hi]

    def boundary_names(self, lo: int, hi: int) -> tuple[list[str], list[str]]:
        """(required_inputs, exported_outputs) for partition [lo, hi).

        required: activations produced before ``lo`` (or the graph input '')
        that nodes in [lo, hi) consume.  exported: activations produced inside
        that nodes at >= hi consume (plus the final node if it is the last).
        """
        inside = {n.name for n in self.nodes[lo:hi]}
        required: list[str] = []
        for n in self.nodes[lo:hi]:
            for inp in n.inputs:
                if inp not in inside and inp not in required:
                    required.append(inp)
        consumed_after = {inp for n in self.nodes[hi:] for inp in n.inputs}
        exported = [n.name for n in self.nodes[lo:hi] if n.name in consumed_after]
        if hi == len(self.nodes) and self.nodes and self.nodes[-1].name not in exported:
            exported.append(self.nodes[-1].name)
        return required, exported
