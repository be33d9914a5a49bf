"""A DEFER compute node (paper Algorithm 2) as a 3-stage internal pipeline.

Each node is one REPLICA of one topology stage: it owns an incoming FIFO
channel (its listening socket — a :class:`~repro.runtime.transport.Channel`
from the stage's transport binding), a reference to the next stage's input
channel (its outgoing socket), and — after the configuration step — a
materialized model partition.  Replicas of the same stage are identical;
the stage's router spreads work across them and this node neither knows
nor cares whether it has siblings.  The paper's
THREAD-1/THREAD-2 pair is generalized into three stages connected by
depth-2 bounded queues (double buffering), so codec work overlaps compute:

    inbox -> [ingress: decode]
          -> _to_compute -> [compute: merge/bucket/stack/apply]
          -> _to_encode  -> [egress: encode ONCE per bucket, relay]
          -> next node's inbox

While batch N runs the jitted partition apply, batch N+1 is deserializing
on the ingress thread and batch N-1 is serializing on the egress thread.
Continuous batching happens at the compute stage: up to ``max_batch``
requests' worth of decoded payloads are merged per step, bucketed by
activation signature (trailing dims + dtype — row counts may be ragged),
concatenated, padded to a power-of-two row count, and computed in ONE
partition apply.  The egress stage then encodes each bucket's stacked
output ONCE — batch-level wire encoding with row-extent framing in the
:class:`BatchEnvelope` — instead of one codec pass per request, so fixed
codec cost amortizes across the batch and the next hop decodes once.

Where bytes never leave the process — the next stage's channel and every
replica behind it are in-process and the data codec is lossless — a
non-tail stage skips the codec and the host altogether: it hands its
outputs on as the ``jax.Array`` values its jitted call returned
(:class:`ResidentRows`, in a resident :class:`BatchEnvelope`), and the next
stage's call consumes them as device futures.  The dispatcher decides
this at wiring time (``resident_out``); the spans ``d2h``, ``serialize``,
``deserialize`` and ``h2d`` then time the hand-off alone.

Failure isolation: an exception in any stage's decode/apply/encode is
caught per batch; the affected requests' extents travel on as an ``error``
envelope (formatted traceback) that downstream stages relay untouched, the
collector fails exactly those futures, and the node keeps serving
subsequent batches.

Each replica keeps one set of window running totals (``stats``, a
:class:`~repro.runtime.spans.Spans` owned as ``stage<i>``): spans where the
work happens (``deserialize``; ``h2d``, ``apply``, ``d2h`` of a stacked
batch or a decode step; a session's whole ``prefill``; the decode wave's
``kv_gather`` (slot lookup and upload) and ``kv_scatter`` (adopting the
written-back slab); ``serialize``), the waits at its three hand-offs
(``inbox`` up to the end of the ingress's coalescing window, ``compute``,
``egress``) and counters (requests, waves, rows, bytes each way, KV slot
occupancy and evictions).  The engine reports the paper's metrics
(compute, overhead, payload) plus the serving ones (per-stage
utilization, queue depth, batch occupancy) from them — and so the
codec/compute overlap is directly measurable.  ``compute_s`` is
``prefill + apply + d2h``.
"""
from __future__ import annotations

import dataclasses
import functools
import queue
import threading
import time
import traceback
import weakref
from typing import Any

import jax
import numpy as np

from repro.core.graph import LayerGraph, LayerNode, SlabRows
from repro.runtime.session import SessionStore
from repro.runtime.spans import Spans
from repro.runtime.transport import Channel, ChannelClosed, InprocChannel
# _STOP / _RETIRE live in wire.py so the byte framing can map them to
# dedicated frame types (a socket transport must carry them too); they are
# re-exported here because the runtime modules treat this as their home.
# _RETIRE drains ONE replica out of a stage without touching the rest of
# the chain: it flows through the replica's internal stages like _STOP —
# so everything already in its queues completes and relays — but the
# egress exits WITHOUT forwarding it downstream, so the next stage's
# _STOP accounting never sees a retired replica.
from repro.runtime.wire import (_RETIRE, _STOP, K_CLOSE,  # noqa: F401
                                K_OPEN, K_PLAIN, K_STEP, BatchEnvelope,
                                ReconfigMarker, RowExtent, WireCodec,
                                WireRecord, first_id, slice_parts,
                                tree_unflatten_paths)


# one replica's running totals (see repro.runtime.spans): spans, waits at
# its hand-offs, counters and gauges.  ``n`` counts requests computed
# (extents), ``rows`` the rows they carried, ``step_rows`` decode-step
# rows; bytes are host<->device copies of activations (a prefill's
# included) and the step waves' slab rows, each counted once read and once
# written: ``kv_bytes`` those of attention KV caches (the step reads them
# in place and writes only their new position), ``state_bytes`` those of
# recurrent (Mamba2) state, which the step reads and writes whole — both
# definitions, not measurements; ``kv_slots_sum`` is the live slots summed
# over step waves (occupancy = kv_slots_sum / apply_n) and
# ``kv_evictions`` the slots LRU reclaimed from a live session.  The
# gauges ``slab_rows`` and ``slab_bytes`` are the slab the replica holds
# (0 before its first open), the byte budget's outcome.  ``encodes``
# counts envelopes framed by the codec, ``resident`` those handed
# downstream on the device instead.
def stage_stats(index: int, lock: threading.Lock | None = None) -> Spans:
    return Spans(f"stage{index}",
                 ("deserialize", "h2d", "apply", "d2h", "prefill",
                  "kv_gather", "kv_scatter", "serialize"),
                 ("inbox", "compute", "egress"),
                 ("n", "waves", "rows", "prefills", "step_rows", "h2d_bytes",
                  "d2h_bytes", "kv_bytes", "state_bytes", "kv_slots_sum",
                  "kv_evictions", "payload_bytes", "encodes", "resident",
                  "busy_compute_s", "depth_sum", "depth_count"),
                 lock, gauges=("slab_rows", "slab_bytes"))


# The share of a device's free memory (its ``bytes_limit`` less
# ``bytes_in_use`` and the slabs other replicas on it have sized but not
# yet allocated) that a replica's slab may take when its rows are sized:
# the rest stays free for prefill temporaries, which grow with the prompt.
SLAB_MEMORY_SHARE = 0.25
_SLAB_LOCK = threading.Lock()
# replicas whose slab is sized but not allocated -> (device, bytes)
_SLAB_PENDING: "weakref.WeakKeyDictionary[Any, tuple[Any, int]]" = \
    weakref.WeakKeyDictionary()


class SlabBudgetError(MemoryError):
    """Even the smallest slab a replica can serve its waves from does not
    fit its share of the device's free memory."""


def free_memory(device) -> int | None:
    """A device's free bytes by its own figures (``bytes_limit`` less
    ``bytes_in_use``), or None where its backend keeps none (the CPU)."""
    stats = device.memory_stats() if device is not None else None
    if not stats or "bytes_limit" not in stats:
        return None
    return stats["bytes_limit"] - stats["bytes_in_use"]


def slab_rows(capacity: int, min_rows: int, row_bytes: int,
              free_bytes: int | None) -> int:
    """A replica's slab rows: ``capacity + 1`` (a slot per resident
    session and the scratch row) where :data:`SLAB_MEMORY_SHARE` of
    ``free_bytes`` holds them, else as many as it holds, never fewer than
    ``min_rows`` (a full wave's sessions and the scratch row).  With no
    memory figure (``free_bytes`` None: a backend without memory stats)
    ``capacity + 1``."""
    if free_bytes is None:
        return capacity + 1
    fit = int(SLAB_MEMORY_SHARE * max(0, free_bytes)) // max(1, row_bytes)
    rows = min(capacity + 1, fit)
    if rows < min_rows:
        raise SlabBudgetError(
            f"a slab of {min_rows} rows of {row_bytes} bytes (a full wave "
            f"and the scratch row) exceeds {SLAB_MEMORY_SHARE:.0%} of the "
            f"device's {free_bytes} free bytes")
    return rows


def stage_view(totals: dict) -> dict:
    """A replica's window totals plus what the controller and the report
    derive from them: ``compute_s`` (prefill + apply + d2h), the stages'
    busy seconds, ``batch_mean`` and ``queue_depth_mean``."""
    waves, depths = totals["waves"], totals["depth_count"]
    return {**totals,
            "compute_s": totals["prefill_s"] + totals["apply_s"]
            + totals["d2h_s"],
            "busy_decode_s": totals["deserialize_s"],
            "busy_encode_s": totals["serialize_s"],
            "batch_mean": totals["n"] / waves if waves else 0.0,
            "queue_depth_mean": (totals["depth_sum"] / depths if depths
                                 else 0.0)}


@dataclasses.dataclass(eq=False)
class ResidentRows:
    """A resident payload, held by reference across an in-process hop:
    rows ``[start, start + rows)`` of one jitted call's outputs, still on
    the device (padding rows included past the call's real rows; a
    close's pass-through payload stays as it came).  A decode step's rows
    also carry ``wide``, the same output padded to the producer's widest
    wave, so rows of several waves gather in one op of one shape
    (:func:`_gather_wide`)."""

    out: dict[str, jax.Array]
    start: int = 0
    rows: int = 1
    wide: jax.Array | None = None

    def host(self) -> dict[str, np.ndarray]:
        """The rows on the host: what the encoded path ships."""
        end = self.start + self.rows
        return {k: np.asarray(v)[self.start:end] for k, v in self.out.items()}


@dataclasses.dataclass
class _Decoded:
    """Ingress -> compute: one inbound envelope, decoded once.  A resident
    envelope's ``boundary`` is its outputs on the device, of which ``src``
    names the envelope's rows."""

    extents: list[RowExtent]
    boundary: dict[str, Any]             # stacked over the envelope's extents
    t_enq: float = 0.0                   # put on the compute queue
    src: ResidentRows | None = None

    @property
    def rows(self) -> int:
        if self.src is not None:
            return self.src.rows
        return next(iter(self.boundary.values())).shape[0]

    def host(self) -> dict[str, np.ndarray]:
        return self.src.host() if self.src is not None else self.boundary


@dataclasses.dataclass
class _Computed:
    """Compute -> egress: one merged batch's bucket outputs, on the host or
    (a resident hop) on the device."""

    buckets: list[tuple[list[RowExtent],
                        "dict[str, np.ndarray] | ResidentRows"]]
    n: int                               # requests in the merged batch
    t_enq: float = 0.0                   # put on the egress queue


def _bucket_rows(n: int) -> int:
    """Next power of two >= n: bounds jit specializations per signature."""
    p = 1
    while p < n:
        p *= 2
    return p


def _leading(tree: dict[str, Any]) -> int:
    return next(iter(tree.values())).shape[0]


def _middle_pads(shape: tuple) -> list[tuple[int, int]]:
    """Zero padding of every middle axis up to the next power of two (none
    for rank <= 2)."""
    if len(shape) <= 2:
        return [(0, 0)] * len(shape)
    return ([(0, 0)] + [(0, _bucket_rows(s) - s) for s in shape[1:-1]]
            + [(0, 0)])


@jax.jit
def _gather_wide(wides: tuple, idx) -> jax.Array:
    """Rows ``idx`` of equally wide outputs laid end to end, in one device
    op.  The caller passes a fixed number of them, so one program per
    wave size serves every mix of upstream waves."""
    return jax.numpy.take(jax.numpy.concatenate(wides, axis=0), idx, axis=0)


@jax.jit
def _pad_middles(tree: dict[str, jax.Array]) -> dict[str, jax.Array]:
    return {k: jax.numpy.pad(v, _middle_pads(v.shape))
            for k, v in tree.items()}


def _signature(boundary: dict[str, np.ndarray]) -> tuple:
    """Bucket key: leaf names + trailing dims + dtypes.  Row counts are
    free to differ — ragged requests concatenate along axis 0."""
    return tuple(sorted((k, v.shape[1:], str(v.dtype))
                        for k, v in boundary.items()))


def _pad_middle(arr: np.ndarray) -> np.ndarray:
    """Zero-pad every middle axis up to the next power of two (no-op for
    rank <= 2 or already-pow2 sizes)."""
    pads = _middle_pads(arr.shape)
    if all(p == (0, 0) for p in pads):
        return arr
    return np.pad(arr, pads)


class ComputeNode:
    """One compute node in the chain."""

    def __init__(self, index: int, data_codec: WireCodec,
                 queue_depth: int = 8, max_batch: int = 8,
                 pad_batches: bool = True, staged: bool = True,
                 stage_depth: int = 2, coalesce_s: float = 0.005,
                 shape_buckets: str = "exact",
                 max_batch_cap: int | None = None,
                 replica: int = 0,
                 inbox: Channel | None = None,
                 session_capacity: int = 64):
        self.index = index              # stage index (ReconfigMarker plans
        self.replica = replica          # are keyed by it); replica id within
        self.data_codec = data_codec    # the stage
        # max_batch and coalesce_s are ADAPTIVE knobs: the serving
        # controller retunes them online from the measured codec/compute
        # stage-time ratio (plain attribute writes; each wave re-reads them)
        self.max_batch = max(1, max_batch)
        self.pad_batches = pad_batches
        self.staged = staged
        self.coalesce_s = coalesce_s
        # "pow2": near-miss trailing shapes merge into one apply via
        # bucketed pad-to-shape (opt-in: requires layers that preserve and
        # act independently along the padded middle axes)
        assert shape_buckets in ("exact", "pow2")
        self.shape_buckets = shape_buckets
        # ceiling for the controller's adaptive max_batch growth;
        # precompile() traces up to the cap so growth never compiles
        # inside a serving window
        self.max_batch_cap = max(self.max_batch, max_batch_cap or 0)
        self.epoch = 0              # last ReconfigMarker this node committed
        self.retiring = False       # drained by scale(), flushing until the
                                    # fence + retire token clear its queues
        self.inbox: Channel = inbox if inbox is not None \
            else InprocChannel(queue_depth)
        self.next_inbox: Channel | None = None
        # hand outputs downstream on the device (resident envelopes) rather
        # than encoded: set by the dispatcher where the next stage's
        # channel and every replica behind it are in-process and the data
        # codec is lossless; read per wave, so a rewiring takes effect on
        # the next one
        self.resident_out = False
        self._egress_epoch = 0      # epoch stamp for outbound envelopes
        self._to_compute: queue.Queue = queue.Queue(maxsize=max(1, stage_depth))
        self._to_encode: queue.Queue = queue.Queue(maxsize=max(1, stage_depth))
        # an item popped for a wave/merge that would overflow max_batch is
        # stashed here and leads the next wave (queues can't push back)
        self._ingress_pending = None
        self._compute_pending = None
        self._stats_lock = threading.Lock()
        # the window's running totals, under the stats lock
        self.stats = stage_stats(index, self._stats_lock)
        self._depth_max = 0         # the window's deepest merge, a max
        self.config_records: list[WireRecord] = []
        self._graph: LayerGraph | None = None
        self._nodes: list[LayerNode] = []
        self._pad_safe = True
        self._params: dict | None = None
        self._required: list[str] = []
        self._exported: list[str] = []
        self._apply = None
        # decode-session state: the slab (per decode layer, one pytree of
        # the prefill cache's structure — KV caches and recurrent states —
        # with ``session_capacity + 1`` rows, fewer where the byte budget
        # says so: a slot per resident session and a scratch row for padded
        # wave rows), allocated at the first open and owned by the compute
        # thread; the session -> slot map (LRU-bounded, see SessionStore);
        # the jitted prefill / slot write / step built only when the graph
        # is decode-capable
        self.sessions = SessionStore(session_capacity)
        self._capacity = self.sessions.capacity     # as configured
        self._slab: dict | None = None
        self._slab_rows: int | None = None          # sized, for this slice
        self._row_bytes = (0, 0)        # one slab row: (KV, recurrent state)
        self._prefill_apply = None
        self._write_slot = None
        self._decode_apply = None
        self._is_tail = False
        self._threads: list[threading.Thread] = []
        # live gauge (NOT a window counter — reset_stats leaves it):
        # requests consumed off the inbox but not yet emitted downstream.
        # A wedged compute thread that swallowed its whole backlog shows
        # inbox qsize 0 (credits returned on consume), so stall detection
        # needs this to see work trapped inside the pipeline.
        self._inflight_n = 0

    # -- configuration step (paper §III-B) ----------------------------------
    def configure(self, graph: LayerGraph, lo: int, hi: int,
                  arch_blob: bytes, weights_blob: bytes,
                  weights_codec: WireCodec) -> None:
        """Receive architecture + weights over the wire and build the model.

        ``graph`` supplies only the layer *functions* (code is pre-installed
        on nodes, as in the paper — TF/Keras is on every device); topology
        and weights come from the wire blobs.
        """
        t0 = time.perf_counter()
        import json
        spec = json.loads(arch_blob.decode())
        flat, dec_s = weights_codec.decode_tree(weights_blob)
        nested = tree_unflatten_paths(flat)
        t1 = time.perf_counter()
        self.config_records.append(
            WireRecord("architecture", len(arch_blob), len(arch_blob), 0.0, 0.0))
        self.config_records.append(
            WireRecord("weights", sum(a.nbytes for a in flat.values()),
                       len(weights_blob), 0.0, t1 - t0))
        self._graph = graph
        self._set_range(lo, hi)
        assert [n.name for n in self._nodes] == spec["layers"], \
            "wire architecture disagrees with local layer code"
        self._params = {k: jax.tree_util.tree_map(jax.numpy.asarray, v)
                        for k, v in nested.items()}
        self._make_apply()

    def _set_range(self, lo: int, hi: int) -> None:
        """Adopt layer range [lo, hi): chain semantics say inbound wire =
        everything crossing the cut before this stage; outbound = everything
        crossing the cut after (includes pass-through activations this
        stage merely relays)."""
        graph = self._graph
        self._nodes = graph.slice_nodes(lo, hi)
        self._required = graph.crossing_names(lo - 1) if lo > 0 else [""]
        self._exported = (graph.crossing_names(hi - 1) if hi < len(graph.nodes)
                          else [graph.nodes[-1].name])
        # the tail stage trims decode outputs to the last position, so a
        # prefill's full-sequence logits never ship past the final hop
        self._is_tail = hi == len(graph.nodes)
        # pow2 pad-to-shape assumes every layer in the slice preserves and
        # acts independently along padded middle axes; a single pad-unsafe
        # layer (attention over the padded axis) makes this segment fall
        # back to exact bucketing
        self._pad_safe = all(n.pad_safe for n in self._nodes)

    def _apply_reconfig(self, marker: ReconfigMarker) -> None:
        """Commit a live repartition at the epoch fence (compute stage).

        Runs on the compute thread exactly when the marker passes it, so
        every envelope ahead of the marker was computed with the old
        partition and every one behind it gets the new — no request sees a
        mixed chain.  Weights arrive as a DIFF: only layers this node
        gains were shipped; layers it keeps are reused in place, layers it
        loses are dropped."""
        plan = marker.plans.get(self.index)
        self.epoch = marker.epoch
        if plan is None:                 # this node's range did not change
            return
        import json
        t0 = time.perf_counter()
        spec = json.loads(plan.arch_blob.decode())
        params = {name: self._params[name] for name in spec["layers"]
                  if name in self._params}
        if plan.weights_blob:
            flat, _ = plan.weights_codec.decode_tree(plan.weights_blob)
            for name, v in tree_unflatten_paths(flat).items():
                params[name] = jax.tree_util.tree_map(jax.numpy.asarray, v)
        self._set_range(plan.lo, plan.hi)
        assert [n.name for n in self._nodes] == spec["layers"], \
            "wire architecture disagrees with local layer code"
        # param-less layers (pool / add / activation nodes) legitimately
        # have no wire entry — only parameterized layers must have arrived
        missing = [n.name for n in self._nodes if n.name not in params
                   and jax.tree_util.tree_leaves(n.param_spec)]
        assert not missing, f"reconfig weights diff is missing {missing}"
        self._params = params
        # the layer slice moved: every resident KV cache is keyed to the
        # OLD slice and is now meaningless — drop them all, slab included.
        # The dispatcher displaces every active session at the same fence,
        # so their generate loops re-prefill instead of stepping into
        # SessionLost.
        self._release_kv()
        self._make_apply()
        self.config_records.append(WireRecord(
            "reconfig", sum(np.asarray(l).nbytes for l in
                            jax.tree_util.tree_leaves(params)),
            plan.wire_bytes, 0.0, time.perf_counter() - t0))

    def _make_apply(self):
        # the stage's weights are an argument of every jitted function
        # (bound with partial), never a closed-over constant: constants
        # are baked into each compiled specialization, so host memory,
        # compile time and device memory would grow with the model once
        # per batch size and prompt length
        nodes, params = self._nodes, self._params
        exported = self._exported

        def apply_fn(params, boundary: dict[str, Any]) -> dict[str, Any]:
            acts = dict(boundary)
            for node in nodes:
                args = [acts[i] for i in node.inputs]
                acts[node.name] = node.fn(params.get(node.name, {}), *args)
            return {n: acts[n] for n in exported}

        self._apply = functools.partial(jax.jit(apply_fn), params)

        # autoregressive view of the same slice: prefill walks the chain
        # once over a full prompt collecting each stateful layer's KV
        # cache; write_slot parks those caches in one slab row; step
        # consumes one token per row (rows may sit at different sequence
        # positions) against the rows' slots of the slab.  The slab is
        # donated to both, so XLA updates it in place, and the slot is a
        # traced argument, so one program serves every slot.  Only built
        # for decode-capable graphs — a pure chain, so the slice has
        # exactly one inbound and one outbound boundary activation.
        self._prefill_apply = None
        self._write_slot = None
        self._decode_apply = None
        self._unsize_slab()
        graph = self._graph
        if (graph is None or not graph.decode_capable or not nodes
                or len(self._required) != 1 or len(exported) != 1):
            return

        # the tail ships one position of logits per open, so past the
        # slice's last stateful layer, where only token-wise layers
        # follow, its prefill keeps the last position alone: the head then
        # computes one row of logits, not one per prompt token
        last = max((i for i, n in enumerate(nodes) if n.decode is not None),
                   default=-1)
        trim = self._is_tail and all(n.pad_safe for n in nodes[last + 1:])

        def prefill_fn(params, x):
            acts = x[:, -1:] if trim and last < 0 else x
            caches = {}
            for i, node in enumerate(nodes):
                p = params.get(node.name, {})
                if node.decode is not None:
                    acts, caches[node.name] = node.decode.prefill_fn(p, acts)
                else:
                    acts = node.fn(p, acts)
                if trim and i == last:
                    acts = acts[:, -1:]
            return acts, caches

        def write_fn(slab, caches, slot):
            return jax.tree_util.tree_map(
                lambda s, c: jax.lax.dynamic_update_slice_in_dim(s, c, slot,
                                                                 0),
                slab, caches)

        # a non-tail step also returns its output padded to the widest
        # wave, which the next stage gathers rows of several waves from
        # when the hop is resident (ResidentRows.wide); the tail ships none
        width = None if self._is_tail else _bucket_rows(self.max_batch_cap)

        def step_fn(params, slab, slots, x, pos):
            acts = x
            new = {}
            for node in nodes:
                p = params.get(node.name, {})
                if node.decode is not None:
                    acts, rows = node.decode.step_fn(
                        p, SlabRows(slab[node.name], slots), acts, pos)
                    new[node.name] = rows.slab
                else:
                    acts = node.fn(p, acts)
            wide = None
            if width is not None:
                wide = jax.numpy.pad(
                    acts, [(0, max(0, width - acts.shape[0]))]
                    + [(0, 0)] * (acts.ndim - 1))
            return acts, wide, new

        self._prefill_apply = functools.partial(jax.jit(prefill_fn), params)
        self._write_slot = jax.jit(write_fn, donate_argnums=0)
        self._decode_apply = functools.partial(
            jax.jit(step_fn, donate_argnums=1), params)

    def _release_kv(self) -> None:
        """Drop every session's residency and the slab with it (a fence,
        a thread exit, a step or slot write that may have consumed the
        donated slab): those sessions' next steps fail ``SessionLost``
        and re-prefill, and the slab's device memory can be freed."""
        self.sessions.clear()
        self._slab = None
        self.stats.set(slab_rows=0, slab_bytes=0)

    def kv_slot_bytes(self) -> int:
        """Device bytes of one slab row: one session's caches and states
        across this slice's decode layers (0 before they are sized)."""
        return sum(self._row_bytes)

    def _size_slab(self, caches: Any) -> int:
        """The slab's rows for this slice, sized once from one session's
        ``caches`` (arrays or shapes, leading axis 1) and the device's
        memory at the time (:func:`slab_rows`); the session store's
        capacity follows, a slot per row but the scratch row."""
        if self._slab_rows is not None:
            return self._slab_rows
        nbytes = lambda tree: sum(
            int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
            for a in jax.tree_util.tree_leaves(tree))
        state = {n.name for n in self._nodes
                 if n.decode is not None and n.decode.recurrent}
        self._row_bytes = (
            nbytes({k: v for k, v in caches.items() if k not in state}),
            nbytes({k: v for k, v in caches.items() if k in state}))
        row = sum(self._row_bytes)
        leaves = jax.tree_util.tree_leaves(self._params or {})
        device = None
        if leaves and isinstance(leaves[0], jax.Array):
            device = next(iter(leaves[0].devices()))   # where the slab goes
        with _SLAB_LOCK:
            free = free_memory(device)
            if free is not None:
                free -= sum(b for node, (d, b) in _SLAB_PENDING.items()
                            if node is not self and d == device)
            rows = slab_rows(self._capacity, self.max_batch_cap + 1, row,
                             free)
            _SLAB_PENDING[self] = (device, rows * row)
        self._slab_rows = rows
        self.sessions.capacity = rows - 1
        return rows

    def _unsize_slab(self) -> None:
        """Forget the slab's size (a new slice sizes its own)."""
        with _SLAB_LOCK:
            _SLAB_PENDING.pop(self, None)
        self._slab_rows = None
        self._row_bytes = (0, 0)
        self.sessions.capacity = self._capacity

    def precompile(self) -> None:
        """Trace/compile every power-of-two padded batch specialization this
        node can hit under continuous batching — the stacked apply AND the
        data codec's own jit (q8's Pallas shapes) — so serving never pays a
        compile inside a measurement window.

        Serving pads bucket totals with ``_bucket_rows`` (pow2 over the
        summed rows), so the traced shapes are ``_bucket_rows(r * base)``
        for every request count r up to max_batch — not r-fold tilings,
        which would miss the padded shapes whenever ``base`` is not itself
        a power of two."""
        if self._apply is None or self._graph is None:
            return
        base: dict[str, np.ndarray] = {}
        for name in self._required:
            spec = (self._graph.input_spec if name == ""
                    else self._graph[name].out_spec)
            base[name] = np.zeros(spec.shape, np.dtype(spec.dtype))
        base_rows = next(iter(base.values())).shape[0]
        seen: set[int] = set()
        r = 1
        while r <= self.max_batch_cap:
            target = (_bucket_rows(r * base_rows) if self.pad_batches
                      else r * base_rows)
            r *= 2
            if target in seen:
                continue
            seen.add(target)
            reps = -(-target // base_rows)
            boundary = {k: jax.numpy.asarray(
                np.concatenate([v] * reps, axis=0)[:target] if reps > 1
                else v[:target])
                for k, v in base.items()}
            outs = self._apply(boundary)
            outs = {k: np.asarray(v) for k, v in outs.items()}
            blob, _ = self.data_codec.encode_tree(outs, "data")
            self.data_codec.decode_tree(blob)
        if self._decode_apply is not None:
            self._precompile_decode()

    def _precompile_decode(self) -> None:
        """Compile the slot write and the decode step for every wave size
        the node can form (pow2-padded up to ``max_batch_cap``) from
        shapes alone: the prefill caches' fixed capacity makes the slab's
        rows the same for any prompt length, and nothing is allocated or
        run.  Per-prompt-length prefills still compile at first use."""
        sds = jax.ShapeDtypeStruct
        name = self._required[0]
        spec = (self._graph.input_spec if name == ""
                else self._graph[name].out_spec)
        _, caches = jax.eval_shape(self._prefill_apply,
                                   sds(spec.shape, spec.dtype))
        n = self._size_slab(caches)
        slab = jax.tree_util.tree_map(
            lambda c: sds((n,) + c.shape[1:], c.dtype), caches)
        self._write_slot.lower(slab, caches, sds((), np.int32)).compile()
        step = self._decode_apply          # partial(jit(step_fn), params)
        for t in sorted({_bucket_rows(b) if self.pad_batches else b
                         for b in range(1, self.max_batch_cap + 1)}):
            rows = sds((t,), np.int32)
            step.func.lower(*step.args, slab, rows,
                            sds((t, 1) + spec.shape[2:], spec.dtype),
                            rows).compile()
            if self.index > 0 and self.data_codec.lossless:
                # an in-process upstream may hand its rows on resident:
                # the one gather a wave of them can need, per wave size
                wide = sds((_bucket_rows(self.max_batch_cap), 1)
                           + spec.shape[2:], spec.dtype)
                _gather_wide.lower((wide,) * self.max_batch_cap,
                                   rows).compile()

    # -- inference step (paper §III-C) ----------------------------------------
    def start(self) -> None:
        if any(t.is_alive() for t in self._threads):
            return
        if self.staged:
            self._threads = [
                threading.Thread(target=self._ingress_loop, daemon=True),
                threading.Thread(target=self._compute_loop, daemon=True),
                threading.Thread(target=self._exit_clearing(self._egress_loop),
                                 daemon=True),
            ]
        else:
            self._threads = [
                threading.Thread(target=self._exit_clearing(self._legacy_loop),
                                 daemon=True)]
        for t in self._threads:
            t.start()

    def _exit_clearing(self, loop):
        """Wrap a replica's final pipeline stage so its exit — stop,
        retire, drain, or a dead link — releases the resident KV slab
        (the compute thread has stopped by then): an exited replica
        serves no further steps, and session recovery is re-prefill
        elsewhere, so the memory must not linger."""
        def run():
            try:
                loop()
            finally:
                self._release_kv()
        return run

    def stop(self) -> None:
        self.inbox.send(_STOP)
        self.join()

    def retire(self) -> None:
        """Queue the single-replica drain token (see ``_RETIRE``).  The
        caller fences routing first; everything already in this replica's
        queues still completes and relays before the threads exit."""
        self.inbox.send(_RETIRE)

    def join(self) -> None:
        for t in self._threads:
            t.join()

    def reset_stats(self) -> None:
        self.stats.reset()
        with self._stats_lock:
            self._depth_max = 0

    def _record_depth(self, depth: int) -> None:
        """Record one merge's queue-depth sample.  Caller holds
        ``_stats_lock``."""
        self.stats.add_locked(depth_sum=depth, depth_count=1)
        self._depth_max = max(self._depth_max, depth)

    def snapshot(self) -> dict:
        """One consistent view of the current measurement window's
        telemetry — what the serving controller calibrates costs and
        adapts knobs from, and what a worker ships in its heartbeats.
        Every total (spans, waits, counters; ``n`` is requests computed
        this window) plus the derived fields of :func:`stage_view`, the
        knobs, the epoch and the in-flight gauge.  O(1)."""
        with self._stats_lock:
            snap = stage_view(self.stats.totals)
            snap["depth_max"] = self._depth_max
            inflight = self._inflight_n
        snap.update(node=self.index, replica=self.replica,
                    max_batch=self.max_batch, coalesce_s=self.coalesce_s,
                    epoch=self.epoch, inflight_n=inflight)
        return snap

    # -- stage 1: ingress (decode) --------------------------------------------
    def _ingress_loop(self) -> None:
        """Drain whatever is already queued (up to max_batch requests),
        decode each envelope once, and hand the whole wave to the compute
        stage — batches form *before* the slow decode, exactly where the
        backlog accumulates, so one wave becomes one apply and one encode."""
        while True:
            env = self._ingress_pending
            self._ingress_pending = None
            if env is None:
                try:
                    env = self.inbox.recv()
                except ChannelClosed:
                    # the inbound link died (socket reset / killed): this
                    # replica can never receive again, so it retires —
                    # everything already decoded flushes, nothing is
                    # signaled downstream (the router proxies its control
                    # tokens), and shutdown can still join its threads
                    self.retiring = True
                    self._to_compute.put(_RETIRE)
                    return
            if env is _STOP or env is _RETIRE:
                self._to_compute.put(env)
                return
            if isinstance(env, ReconfigMarker):
                # the epoch fence rides the FIFO: decode is partition-
                # independent, so ingress just relays it in order
                self._to_compute.put(env)
                continue
            wave = [env]
            n_parts = env.n if env.error is None else 0
            saw_stop = None
            deadline = None
            while n_parts < self.max_batch:
                try:
                    nxt = self.inbox.recv_nowait()
                except queue.Empty:
                    # downstream still chewing on the previous wave: a
                    # bounded coalescing window grows this wave instead of
                    # queueing a tiny one behind it (bigger waves = fewer
                    # codec passes; compute is busy so latency cost ~ 0)
                    if self._to_compute.qsize() == 0:
                        break
                    now = time.perf_counter()
                    if deadline is None:
                        deadline = now + self.coalesce_s
                    if now >= deadline:
                        break
                    try:
                        nxt = self.inbox.recv(timeout=deadline - now)
                    except queue.Empty:
                        continue
                    except ChannelClosed:
                        self.retiring = True
                        saw_stop = _RETIRE      # flush this wave, then exit
                        break
                except ChannelClosed:
                    self.retiring = True
                    saw_stop = _RETIRE
                    break
                if nxt is _STOP or nxt is _RETIRE:
                    saw_stop = nxt
                    break
                if isinstance(nxt, ReconfigMarker):
                    # close the wave at the fence; the marker leads the
                    # next iteration so it stays ordered behind this wave
                    self._ingress_pending = nxt
                    break
                if nxt.error is None and n_parts + nxt.n > self.max_batch:
                    # would overflow the batch contract (and the pow2
                    # specializations precompile() traced): next wave's
                    self._ingress_pending = nxt
                    break
                wave.append(nxt)
                if nxt.error is None:
                    n_parts += nxt.n
            # the wave is closed: its envelopes' inbox wait ends here.  Only
            # codec time is decode busy (the deserialize span) — the queue
            # puts below can block on backpressure, which is waiting
            now = time.perf_counter()
            for env in wave:
                self.stats.waited("inbox", env.t_enq, env.n, now)
            decoded: list[_Decoded] = []
            relay: list[BatchEnvelope] = []
            for env in wave:
                if env.error is not None:       # relay failures untouched
                    relay.append(env)
                    continue
                with self.stats.span("deserialize", rid=first_id(env.extents),
                                     rows=env.n):
                    if env.resident is not None:    # adopted as it is
                        decoded.append(_Decoded(env.extents,
                                                env.resident.out,
                                                src=env.resident))
                        continue
                    try:
                        flat, _ = self.data_codec.decode_tree(env.blob)
                        decoded.append(_Decoded(
                            env.extents,
                            {k: np.asarray(v) for k, v in flat.items()}))
                    except Exception:
                        relay.append(BatchEnvelope(
                            env.extents, b"", error=traceback.format_exc()))
            with self._stats_lock:
                self._inflight_n += sum(len(e.extents) for e in wave)
            for env in relay:
                self._to_compute.put(env)
            if decoded:
                now = time.perf_counter()
                for d in decoded:
                    d.t_enq = now
                self._to_compute.put(decoded)
            if saw_stop is not None:
                self._to_compute.put(saw_stop)
                return

    # -- stage 2: compute (merge, bucket, stack, apply) -----------------------
    def _compute_loop(self) -> None:
        while True:
            item = self._compute_pending
            self._compute_pending = None
            if item is None:
                item = self._to_compute.get()
            if item is _STOP or item is _RETIRE:
                self._to_encode.put(item)
                return
            if isinstance(item, ReconfigMarker):
                # the fence reached the compute stage: swap partitions NOW
                # (everything ahead of it already computed on the old one)
                self._apply_reconfig(item)
                self._to_encode.put(item)
                continue
            if isinstance(item, BatchEnvelope):  # error passthrough
                self._to_encode.put(item)
                continue
            # continuous batching, second chance: merge any further decoded
            # waves, up to max_batch requests, without waiting for arrivals
            group = list(item)
            n_parts = sum(len(d.extents) for d in group)
            saw_stop = None
            while n_parts < self.max_batch:
                try:
                    nxt = self._to_compute.get_nowait()
                except queue.Empty:
                    break
                if nxt is _STOP or nxt is _RETIRE:
                    saw_stop = nxt
                    break
                if isinstance(nxt, ReconfigMarker):
                    self._compute_pending = nxt    # fence: no merging across
                    break
                if isinstance(nxt, BatchEnvelope):
                    self._to_encode.put(nxt)
                    continue
                add = sum(len(d.extents) for d in nxt)
                if n_parts + add > self.max_batch:
                    self._compute_pending = nxt     # next merge's
                    break
                group.extend(nxt)
                n_parts += add
            t0 = time.perf_counter()
            for d in group:
                self.stats.waited("compute", d.t_enq, len(d.extents), t0)
            with self._stats_lock:
                self._record_depth(n_parts + self.inbox.qsize()
                                   + self._to_compute.qsize())
            out, failures = self._compute_group(group)
            self.stats.add(busy_compute_s=time.perf_counter() - t0)
            for env in failures:
                self._to_encode.put(env)
            if out is not None:
                out.t_enq = time.perf_counter()
                self._to_encode.put(out)
            if saw_stop is not None:
                self._to_encode.put(saw_stop)
                return

    def _pad_to_bucket(self, d: _Decoded) -> _Decoded:
        """Zero-pad a decoded segment's middle axes up to the pow2 bucket
        sizes, recording each extent's ORIGINAL sizes the first time it is
        padded (later hops see already-pow2 shapes, so padding there is a
        no-op and the original trim is preserved).

        One ``pad_trim`` describes every leaf of the request, so a
        boundary whose leaves disagree on middle-axis sizes (e.g. a cut
        crossed by several pass-through activations) is left unpadded —
        it falls back to exact bucketing rather than risking a trim that
        slices real rows off a sibling leaf."""
        mids = {tuple(v.shape[1:-1]) for v in d.boundary.values()
                if v.ndim > 2}
        if len(mids) != 1:
            return d
        orig_mid = next(iter(mids))
        if all(s == _bucket_rows(s) for s in orig_mid):
            return d
        extents = [e if e.pad_trim is not None
                   else dataclasses.replace(e, pad_trim=orig_mid)
                   for e in d.extents]
        if d.src is not None:                   # on the device, one op
            padded = _pad_middles(d.boundary)
            return _Decoded(extents, padded, d.t_enq,
                            dataclasses.replace(d.src, out=padded))
        padded = {k: _pad_middle(v) for k, v in d.boundary.items()}
        return _Decoded(extents, padded, d.t_enq)

    def _assemble(self, segs: list[_Decoded], total: int,
                  target: int) -> tuple[dict[str, Any], int]:
        """A bucket's ``target``-row input on the device, and the bytes
        uploaded for it.  One upstream bucket, resident, takes no op: its
        rows as computed, in order, padding rows riding along (every
        layer is row-independent, so they touch no real row).  Encoded
        segments, and resident ones of several upstream buckets, are
        concatenated and zero-padded on the host and uploaded."""
        if len(segs) == 1 and segs[0].src is not None:
            src = segs[0].src
            if src.start == 0 and _leading(src.out) == target:
                return src.out, 0
        parts = [d.host() for d in segs]
        stacked: dict[str, jax.Array] = {}
        up = 0
        for key in parts[0]:
            arrs = [p[key] for p in parts]
            cat = np.concatenate(arrs, axis=0) if len(arrs) > 1 else arrs[0]
            if target > total:
                pad = np.zeros((target - total,) + cat.shape[1:], cat.dtype)
                cat = np.concatenate([cat, pad], axis=0)
            up += cat.nbytes
            stacked[key] = jax.numpy.asarray(cat)
        return stacked, up

    def _stack_apply(self, segs: list[_Decoded], total: int, target: int,
                     rid: int, keep: bool = False
                     ) -> "dict[str, np.ndarray] | ResidentRows":
        """Stack the segments' rows into ``target`` rows (:meth:`_assemble`),
        run the jitted partition apply once, and pull the outputs to the
        host trimmed back to ``total`` rows — or, with ``keep`` (a
        resident hop), hand them on as they are.  Shared by the staged
        compute stage and the legacy per-request path.  ``rid`` (the first
        request's id) rides the spans' metadata."""
        stats = self.stats
        with stats.span("h2d", rid=rid, rows=total):
            stacked, up = self._assemble(segs, total, target)
        # no span waits on the device: ``apply`` is the call, and ``d2h``'s
        # np.asarray waits for the upload, the computation and the copy out
        with stats.span("apply", rid=rid, rows=total):
            res = self._apply(stacked)
        with stats.span("d2h", rid=rid, rows=total):
            if keep:
                out, down = ResidentRows(res, 0, total), 0
            else:
                res = {k: np.asarray(v) for k, v in res.items()}
                down = sum(v.nbytes for v in res.values())
                out = {k: v[:total] for k, v in res.items()}
        stats.add(rows=total, h2d_bytes=up, d2h_bytes=down)
        return out

    def _compute_group(self, group: list[_Decoded]
                       ) -> tuple[_Computed | None, list[BatchEnvelope]]:
        """Bucket decoded segments by signature, one stacked apply each.

        A bucket whose apply raises becomes an error envelope for exactly
        its own extents; sibling buckets in the merged group still return
        their results.

        With ``shape_buckets='pow2'``, near-miss trailing shapes are first
        zero-padded along their middle axes to the bucket's power-of-two
        sizes, so e.g. ragged sequence lengths merge into ONE apply instead
        of one bucket each; the original sizes ride the extents
        (``pad_trim``) and the tail collector trims them back out."""
        n = sum(len(d.extents) for d in group)
        keep = self.resident_out and not self._is_tail
        # session frames (kind != K_PLAIN) take the decode path; plain
        # traffic keeps the stacked-apply path.  Both run inside the same
        # merged wave, so a chain can serve single-shot and decode traffic
        # simultaneously off one set of replicas.
        plain: list[_Decoded] = []
        sess: list[_Decoded] = []
        for d in group:
            (sess if any(e.kind != K_PLAIN for e in d.extents)
             else plain).append(d)
        outs: list[tuple[list[RowExtent], Any]] = []
        failures: list[BatchEnvelope] = []
        if sess:
            s_out, s_fail = self._decode_group(sess, keep)
            outs.extend(s_out)
            failures.extend(s_fail)
        if self.shape_buckets == "pow2" and self._pad_safe:
            # only when every layer in this replica's slice is pad_safe:
            # a segment containing e.g. attention over the middle axis
            # would see padded positions, so it stays on exact bucketing
            plain = [self._pad_to_bucket(d) for d in plain]
        buckets: dict[tuple, list[_Decoded]] = {}
        for d in plain:
            buckets.setdefault(_signature(d.boundary), []).append(d)

        for segs in buckets.values():
            extents = [e for d in segs for e in d.extents]
            total = sum(d.rows for d in segs)
            target = _bucket_rows(total) if self.pad_batches else total
            try:
                res = self._stack_apply(segs, total, target,
                                        first_id(extents), keep)
            except Exception:
                failures.append(BatchEnvelope(extents, b"",
                                              error=traceback.format_exc()))
                continue
            outs.append((extents, res))
        if not outs:
            return None, failures
        return _Computed(outs, n), failures

    def _decode_group(self, group: list[_Decoded], keep: bool = False
                      ) -> tuple[list, list[BatchEnvelope]]:
        """Serve one merged wave's session traffic (kind != K_PLAIN).

        A resident session is one slot of this replica's KV slab, named by
        its :class:`SessionStore`.  Closes free the session's slot and
        pass their payload through untouched (each stage on the way to the
        tail frees in turn).  Opens run the slice's prefill individually
        (B=1 — jit specializes per prompt length) and write the caches
        into the session's slot with one jitted call that donates the slab
        (allocated at the replica's first open from those caches' shapes);
        the tail stage trims its output to the last position so only one
        row of logits ships.  Steps batch ACROSS sessions: ONE jitted call
        takes the slab (donated), the wave's slots, tokens and positions
        and runs the slice's step on those rows: each layer reads its rows
        in place (``decode_attention`` picks them by slot) and writes only
        their new position — continuous batching of decode at *different*
        sequence positions, whose host-side KV work is one small upload of
        slot indices.  Padding rows read and write the slab's scratch row,
        never a live slot.  A step whose session holds no slot here (evicted,
        repartitioned, replica restarted) fails with a ``SessionLost``
        error envelope; recovery is the generate loop's re-prefill, never
        a replay.  A slot write or step that raises may have consumed the
        donated slab, so it drops every session of the replica, and each
        re-prefills.

        Session envelopes carry exactly one extent by protocol (routers
        pin whole envelopes; a multi-session envelope could not route
        sticky), enforced here.

        With ``keep`` (a resident hop) the outputs stay on the device: an
        open's whole, each step row as a :class:`ResidentRows` of the wave's.

        Returns ``(outs, failures)``.
        """
        stats = self.stats
        outs: list[tuple[list[RowExtent], Any]] = []
        failures: list[BatchEnvelope] = []
        out_name = self._exported[0] if self._exported else ""
        steps: list[tuple[RowExtent, Any]] = []
        for d in group:
            if len(d.extents) != 1:
                failures.append(BatchEnvelope(
                    d.extents, b"",
                    error="decode protocol violation: a session envelope "
                          "must carry exactly one extent"))
                continue
            e = d.extents[0]
            if e.kind == K_CLOSE:
                self.sessions.pop(e.session)
                outs.append(([e], d.src or d.boundary))
                continue
            if self._prefill_apply is None:
                failures.append(BatchEnvelope(
                    [e], b"",
                    error="SessionUnsupported: this partition has no "
                          "autoregressive view (the graph declares no "
                          "LayerDecode nodes, or the slice is not a "
                          "single-boundary chain)"))
                continue
            x = d.src if d.src is not None else next(iter(d.boundary.values()))
            if e.kind == K_OPEN:
                with stats.span("prefill", rid=e.request_id, rows=d.rows):
                    try:
                        if d.src is None:
                            xin, up = jax.numpy.asarray(x), x.nbytes
                        else:
                            tree, up = self._assemble([d], d.rows, d.rows)
                            xin = next(iter(tree.values()))
                        y, caches = self._prefill_apply(xin)
                        if keep:
                            y, down = ResidentRows({out_name: y}, 0,
                                                 y.shape[0]), 0
                        else:
                            y = np.asarray(y)
                            down = y.nbytes
                    except Exception:
                        failures.append(BatchEnvelope(
                            [e], b"", error=traceback.format_exc()))
                        continue
                    # a slot even when the slice holds no stateful layer
                    # (caches == {}): residency doubles as the routing
                    # check a later step validates against
                    try:
                        evicted = self._park(e.session, caches)
                    except Exception:
                        self._release_kv()
                        failures.append(BatchEnvelope(
                            [e], b"", error=traceback.format_exc()))
                        continue
                stats.add(prefills=1, rows=d.rows, h2d_bytes=up,
                          d2h_bytes=down, kv_evictions=int(evicted))
                if keep:
                    outs.append(([e], y))
                    continue
                if self._is_tail:
                    y = y[:, -1:]
                outs.append(([e], {out_name: y}))
            elif e.kind == K_STEP:
                steps.append((e, x))
            else:
                failures.append(BatchEnvelope(
                    [e], b"",
                    error=f"unknown session frame kind {e.kind}"))
        if not steps:
            return outs, failures
        rid = steps[0][0].request_id
        with stats.span("kv_gather", rid=rid, rows=len(steps)):
            live: list[tuple[RowExtent, Any, int]] = []
            for e, x in steps:
                slot = self.sessions.get(e.session)
                if slot is None:
                    failures.append(BatchEnvelope([e], b"", error=(
                        f"SessionLost: stage {self.index} replica "
                        f"{self.replica} holds no KV slot for session "
                        f"{e.session!r} (evicted, repartitioned, or the "
                        "replica restarted); re-open the session from "
                        "its retained history")))
                else:
                    live.append((e, x, slot))
            if not live:
                return outs, failures
            b = len(live)
            target = _bucket_rows(b) if self.pad_batches else b
            slots = jax.numpy.asarray(np.asarray(
                [s for _, _, s in live]
                + [self.sessions.capacity] * (target - b), np.int32))
        # padding rows repeat the last row's token and position against the
        # scratch row: decode arithmetic is row-independent, so the real
        # rows are bit-identical to an unpadded apply
        rows = live + [live[-1]] * (target - b)
        with stats.span("h2d", rid=rid, rows=b):
            xs, up = self._assemble_steps([x for _, x, _ in live], target)
            pos = jax.numpy.asarray(
                np.asarray([e.pos for e, _, _ in rows], np.int32))
        try:
            with stats.span("apply", rid=rid, rows=b):
                y, wide, slab = self._decode_apply(self._slab, slots, xs,
                                                   pos)
            with stats.span("d2h", rid=rid, rows=b):    # as in _stack_apply
                if keep:
                    tree = {out_name: y}
                    ys = [ResidentRows(tree, i, 1, wide) for i in range(b)]
                    down = 0
                else:
                    y = np.asarray(y)
                    ys = [{out_name: y[i:i + 1]} for i in range(b)]
                    down = y.nbytes
        except Exception:
            # the call may have consumed the donated slab: no step may run
            # on it again, so every session here re-prefills
            self._release_kv()
            tb = traceback.format_exc()
            failures.extend(BatchEnvelope([e], b"", error=tb)
                            for e, _, _ in live)
            return outs, failures
        with stats.span("kv_scatter", rid=rid, rows=b):
            self._slab = slab
            resident = len(self.sessions)
        # device bytes: every row's slot, counted read and written
        kv_row, state_row = self._row_bytes
        stats.add(rows=b, step_rows=b, h2d_bytes=up + pos.nbytes,
                  d2h_bytes=down, kv_bytes=2 * target * kv_row,
                  state_bytes=2 * target * state_row, kv_slots_sum=resident)
        outs.extend(([e], y) for (e, _, _), y in zip(live, ys))
        return outs, failures

    def _assemble_steps(self, xs: list, target: int) -> tuple[Any, int]:
        """A step wave's ``target``-row input on the device, and the bytes
        uploaded for it; padding rows repeat the last row.  Resident rows
        take no op where they are one upstream wave's rows as computed (in
        order, its padding rows riding along), else ONE gather from their
        waves' wide outputs (:func:`_gather_wide`, its arity fixed at
        ``max_batch_cap`` so one program per wave size serves any mix).
        Encoded rows, or resident rows without one width, go through the
        host."""
        b = len(xs)
        if all(isinstance(x, ResidentRows) for x in xs):
            first = xs[0]
            y = next(iter(first.out.values()))
            if y.shape[0] == target and all(
                    x.out is first.out and x.start == i
                    for i, x in enumerate(xs)):
                return y, 0
            wides = list({id(x.wide): x.wide for x in xs}.values())
            if (all(x.wide is not None for x in xs)
                    and len(wides) <= self.max_batch_cap
                    and len({w.shape for w in wides}) == 1):
                at = {id(w): j * w.shape[0] for j, w in enumerate(wides)}
                idx = [at[id(x.wide)] + x.start for x in xs]
                idx += [idx[-1]] * (target - b)
                wides += [wides[0]] * (self.max_batch_cap - len(wides))
                return _gather_wide(tuple(wides),
                                    np.asarray(idx, np.int32)), 0
        rows = [next(iter(x.host().values())) if isinstance(x, ResidentRows)
                else x for x in xs]
        cat = np.concatenate(rows + [rows[-1]] * (target - b), axis=0)
        return jax.numpy.asarray(cat), cat.nbytes

    def _park(self, session: Any, caches: Any) -> bool:
        """Write a session's prefill caches into its slot, allocating the
        slab at the replica's first open (the caches' fixed capacity makes
        every prompt length give the same rows; their number is budgeted
        by bytes, :meth:`_size_slab`).  Returns whether LRU evicted a
        live session for the slot."""
        if self._slab is None:
            n = self._size_slab(caches)
            self._slab = jax.tree_util.tree_map(
                lambda c: jax.numpy.zeros((n,) + c.shape[1:], c.dtype),
                caches)
            with _SLAB_LOCK:
                _SLAB_PENDING.pop(self, None)
            self.stats.set(slab_rows=n, slab_bytes=n * self.kv_slot_bytes())
        slot, evicted = self.sessions.claim(session)
        self._slab = self._write_slot(self._slab, caches, np.int32(slot))
        return evicted

    # -- stage 3: egress (encode once per bucket, relay) ----------------------
    def _relay(self, item: Any) -> None:
        """Send one item downstream.

        A DEAD downstream link (socket reset) is swallowed: the item is
        lost either way — the chain is already severed past this hop —
        and an egress thread dying on the send would leave the internal
        queues undrained and deadlock shutdown on top of the network
        failure.  Any OTHER send failure (e.g. a payload the byte framing
        refuses) is per-batch: the envelope's extents travel on as an
        error envelope so the collector fails exactly those futures
        instead of the request silently hanging."""
        if self.next_inbox is None:
            return
        if isinstance(item, BatchEnvelope):
            item.t_enq = time.perf_counter()
        try:
            self.next_inbox.send(item)
        except (ChannelClosed, OSError):
            pass
        except Exception:
            if not isinstance(item, BatchEnvelope):
                return          # tokens/markers always frame: link fault
            try:
                self.next_inbox.send(BatchEnvelope(
                    item.extents, b"", error=traceback.format_exc(),
                    epoch=item.epoch))
            except Exception:  # deferlint: swallow(error envelope itself unencodable; no further signal possible)
                pass

    def _egress_loop(self) -> None:
        while True:
            item = self._to_encode.get()
            if item is _RETIRE:
                # single-replica drain: exit WITHOUT forwarding — the
                # downstream stage must not count a retired replica's stop
                return
            if item is _STOP:
                self._relay(_STOP)
                return
            if isinstance(item, ReconfigMarker):
                # epoch fence: everything encoded after this point was
                # computed on the new partition — stamp it so the next
                # stage's router can hold it behind its own fence barrier
                self._egress_epoch = item.epoch
                self._relay(item)
                continue
            if isinstance(item, BatchEnvelope):
                # error passthrough: relay in order, stamped
                item.epoch = self._egress_epoch
                with self._stats_lock:
                    self._inflight_n -= len(item.extents)
                self._relay(item)
                continue
            # only codec time is encode busy (the serialize span); the relay
            # puts can block on the next node's bounded inbox (backpressure)
            self.stats.waited("egress", item.t_enq, item.n)
            payload = encodes = resident = 0
            out_envs: list[BatchEnvelope] = []
            for extents, res in item.buckets:
                with self.stats.span("serialize", rid=extents[0].request_id,
                                     rows=len(extents)):
                    if self.resident_out:       # by reference, no bytes
                        if not isinstance(res, ResidentRows):
                            res = ResidentRows(res, 0, _leading(res))
                        out_envs.append(BatchEnvelope(
                            extents, b"", epoch=self._egress_epoch,
                            resident=res))
                        resident += 1
                        continue
                    try:
                        if isinstance(res, ResidentRows):  # rewired since
                            res = res.host()
                        blob, rec = self.data_codec.encode_tree(
                            res, "data", request_id=extents[0].request_id,
                            client_id=extents[0].client_id)
                        env = BatchEnvelope(extents, blob,
                                            epoch=self._egress_epoch)
                        payload += rec.wire_bytes
                        encodes += 1
                    except Exception:
                        env = BatchEnvelope(extents, b"",
                                            error=traceback.format_exc(),
                                            epoch=self._egress_epoch)
                out_envs.append(env)
            with self._stats_lock:
                self.stats.add_locked(n=item.n, waves=1,
                                      payload_bytes=payload, encodes=encodes,
                                      resident=resident)
                self._inflight_n -= sum(len(e.extents) for e in out_envs)
            for env in out_envs:
                self._relay(env)

    # -- unstaged path (the PR 1 baseline, kept for A/B benchmarks) -----------
    def _legacy_loop(self) -> None:
        """Single worker thread: read -> decode -> apply -> encode PER
        REQUEST -> relay, the pre-staged hot path.  Kept so
        ``benchmarks/serve_load.py`` can measure the staged pipeline against
        the same-codec PR 1 baseline in one process."""
        while True:
            try:
                item = self.inbox.recv()
            except ChannelClosed:
                self.retiring = True     # dead inbound link: self-retire
                return
            if item is _RETIRE:
                return                   # drain this replica only: no relay
            if item is _STOP:
                self._relay(_STOP)
                return
            if isinstance(item, ReconfigMarker):
                self._apply_reconfig(item)
                self._egress_epoch = item.epoch
                self._relay(item)
                continue
            batch = [item]
            saw_stop = False
            retire = False
            marker = None
            while sum(e.n for e in batch) < self.max_batch:
                try:
                    nxt = self.inbox.recv_nowait()
                except queue.Empty:
                    break
                except ChannelClosed:
                    self.retiring = True
                    retire = True        # flush this batch, then exit
                    break
                if nxt is _STOP:
                    saw_stop = True
                    break
                if nxt is _RETIRE:
                    retire = True
                    break
                if isinstance(nxt, ReconfigMarker):
                    marker = nxt         # fence: swap after this batch
                    break
                batch.append(nxt)
            with self._stats_lock:
                self._record_depth(len(batch) + self.inbox.qsize())
                self._inflight_n += sum(len(e.extents) for e in batch)
            outs = self.process_batch(batch)
            with self._stats_lock:
                self._inflight_n -= sum(len(e.extents) for e in outs)
            for env in outs:
                env.epoch = self._egress_epoch
                self._relay(env)
            if marker is not None:
                self._apply_reconfig(marker)
                self._egress_epoch = marker.epoch
                self._relay(marker)
            if retire:
                return
            if saw_stop:
                self._relay(_STOP)
                return

    def process_batch(self, envs: list[BatchEnvelope]) -> list[BatchEnvelope]:
        """Decode, bucket-by-shape, pad, compute once, split, re-encode each
        request separately (per-request wire, PR 1 semantics)."""
        stats = self.stats
        passthrough = [e for e in envs if e.error is not None]
        work = [e for e in envs if e.error is None]
        samples: list[tuple[RowExtent, dict[str, np.ndarray]]] = []
        failed: list[BatchEnvelope] = []
        for env in work:
            if any(ext.kind != K_PLAIN for ext in env.extents):
                # session residency needs the staged pipeline's sticky
                # decode path; the per-request legacy path has neither
                failed.append(BatchEnvelope(
                    env.extents, b"",
                    error="decode sessions require the staged runtime "
                          "(ComputeNode(staged=True))"))
                continue
            with stats.span("deserialize", rid=first_id(env.extents),
                            rows=env.n):
                try:
                    flat, _ = self.data_codec.decode_tree(env.blob)
                    flat = {k: np.asarray(v) for k, v in flat.items()}
                except Exception:
                    failed.append(BatchEnvelope(env.extents, b"",
                                                error=traceback.format_exc()))
                    continue
            for ext, part in zip(env.extents, slice_parts(flat, env.extents)):
                samples.append((ext, part))

        buckets: dict[tuple, list[tuple[RowExtent, dict]]] = {}
        for ext, boundary in samples:
            buckets.setdefault(_signature(boundary), []).append((ext, boundary))

        out_envs: list[BatchEnvelope] = list(passthrough) + failed
        payload_total = 0
        encodes = 0
        for bucket in buckets.values():
            rows = [next(iter(b.values())).shape[0] for _, b in bucket]
            total = sum(rows)
            target = _bucket_rows(total) if self.pad_batches else total
            t0 = time.perf_counter()
            try:
                outs = self._stack_apply(
                    [_Decoded([ext], b) for ext, b in bucket], total,
                    target, bucket[0][0].request_id)
            except Exception:
                tb = traceback.format_exc()
                out_envs.extend(BatchEnvelope([ext], b"", error=tb)
                                for ext, _ in bucket)
                continue
            finally:
                stats.add(busy_compute_s=time.perf_counter() - t0)
            off = 0
            for (ext, _), b_rows in zip(bucket, rows):
                piece = {k: v[off:off + b_rows] for k, v in outs.items()}
                off += b_rows
                with stats.span("serialize", rid=ext.request_id, rows=1):
                    try:
                        blob, rec = self.data_codec.encode_tree(
                            piece, "data", request_id=ext.request_id,
                            client_id=ext.client_id)
                        payload_total += rec.wire_bytes
                        encodes += 1
                        out_envs.append(BatchEnvelope([ext], blob))
                    except Exception:
                        out_envs.append(BatchEnvelope(
                            [ext], b"", error=traceback.format_exc()))
        stats.add(n=len(samples), waves=1, payload_bytes=payload_total,
                  encodes=encodes)
        return out_envs
