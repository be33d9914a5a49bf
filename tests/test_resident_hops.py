"""Resident hops: where a hop never leaves the process (in-process channel,
in-process staged replicas, a lossless data codec) a non-tail stage hands
its outputs to the next as device arrays, never through the codec or the
host.  The outputs equal the encoded path's; a lossy codec, a framing
transport and a worker replica keep the encoded path; the fences, errors
and SessionLost behave as before; the resident form never reaches a
socket."""
import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.lm_graph import pipeline_decode_reference
from repro.runtime import InferenceEngine, TopologySpec
from repro.runtime.dispatcher import DispatcherCodecs, NodeError
from repro.runtime.node import ComputeNode, ResidentRows, _Decoded
from repro.runtime.session import SessionLost
from repro.runtime.transport import get_transport
from repro.runtime.wire import (BatchEnvelope, RowExtent, WireCodec,
                                WireFormatError, frame)
from tests._worker_graphs import D, lm_graph, mlp_graph, poison_graph

RAW = DispatcherCodecs(data=WireCodec("raw", "none"),
                       weights=WireCodec("raw", "none"))
Q8 = DispatcherCodecs(data=WireCodec("q8", "none"),
                      weights=WireCodec("raw", "none"))
ROWS = [1, 3, 2, 1, 4, 1]


def sample(i: int, rows: int = 1) -> np.ndarray:
    return np.random.default_rng(i).normal(size=(rows, D)).astype(np.float32)


class CountingCodec:
    """A node's data codec that counts its calls."""

    def __init__(self, codec):
        self._codec = codec
        self.calls = 0

    def __getattr__(self, name):
        return getattr(self._codec, name)

    def encode_tree(self, *a, **kw):
        self.calls += 1
        return self._codec.encode_tree(*a, **kw)

    def decode_tree(self, *a, **kw):
        self.calls += 1
        return self._codec.decode_tree(*a, **kw)


def serve_oneshot(transports, codecs=RAW, count_codec=False):
    """A 3-stage chain of ``mlp_graph(6)``, one stage transport each; the
    outputs of ROWS-shaped requests sent one at a time, the replicas'
    totals and (``count_codec``) each replica's codec calls."""
    g = mlp_graph(6)
    params = g.init(jax.random.PRNGKey(0))
    topo = TopologySpec.chain(g, 3)
    topo = TopologySpec(tuple(dataclasses.replace(s, transport=t)
                              for s, t in zip(topo.stages, transports)))
    eng = InferenceEngine(g, topo, codecs, max_batch=4)
    eng.configure(params)
    nodes = eng.dispatcher.nodes
    counters = []
    if count_codec:
        for n in nodes:
            n.data_codec = CountingCodec(n.data_codec)
            counters.append(n.data_codec)
    eng.start()
    eng.reset_window()
    try:
        outs = [eng.submit(sample(i, r)).result(timeout=60)
                for i, r in enumerate(ROWS)]
        rep = eng.report()
        flags = [n.resident_out for n in nodes]
    finally:
        eng.shutdown()
    return (g, params, outs, [pn["totals"] for pn in rep.per_node],
            [c.calls for c in counters], flags)


def test_oneshot_chain_stays_on_the_device_and_matches_the_encoded_path():
    g, params, outs, totals, calls, flags = serve_oneshot(
        ["inproc"] * 3, count_codec=True)
    *_, tcp_outs, tcp_totals, _, tcp_flags = serve_oneshot(["tcp"] * 3)
    for i, (r, out, enc) in enumerate(zip(ROWS, outs, tcp_outs)):
        assert out.shape == (r, D)
        np.testing.assert_array_equal(out, enc)
        ref = np.asarray(g.apply(params, jnp.asarray(sample(i, r))))
        np.testing.assert_allclose(out, ref, atol=1e-5)
    assert flags == [True, True, False]
    assert tcp_flags == [False, False, False]
    n = len(ROWS)
    # every non-tail hand-off resident, only the tail encodes
    assert [(t["resident"], t["encodes"]) for t in totals] == [
        (n, 0), (n, 0), (0, n)]
    assert [(t["resident"], t["encodes"]) for t in tcp_totals] == [(0, n)] * 3
    # stage 0 decodes the dispatcher's input, the tail encodes for the
    # collector; nothing else touches the codec
    assert calls == [n, 0, n]
    assert totals[0]["payload_bytes"] == totals[1]["payload_bytes"] == 0
    assert totals[0]["d2h_bytes"] == totals[1]["h2d_bytes"] == 0


@pytest.mark.parametrize("codecs, transports, resident", [
    (Q8, ["inproc"] * 3, [False, False, False]),
    (RAW, ["inproc", "tcp", "inproc"], [False, True, False]),
], ids=["q8", "tcp-stage"])
def test_encoded_path_where_bytes_matter(codecs, transports, resident):
    """A lossy codec encodes at every hop, and a hop into a framing stage
    is encoded; the outputs equal the all-tcp chain's."""
    *_, outs, totals, _, flags = serve_oneshot(transports, codecs)
    *_, ref_outs, _, _, _ = serve_oneshot(["tcp"] * 3, codecs)
    assert flags == resident
    for t, on in zip(totals, resident):
        assert (t["resident"] > 0) == on
        assert t["encodes"] == (0 if on else len(ROWS))
    for out, ref in zip(outs, ref_outs):
        np.testing.assert_array_equal(out, ref)


def test_decode_sessions_are_token_identical():
    """Three concurrent sessions through a 2-stage decoder (waves of up to
    three rows, padded to four): every open, step and close crosses the
    inner hop on the device, and the tokens equal the reference's."""
    g = lm_graph()
    params = g.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(g, TopologySpec.chain(g, 2), RAW, max_batch=4)
    eng.configure(params)
    eng.precompile()
    prompts, m = [[1, 5, 9, 2], [3, 3, 7], [2, 8, 4, 6, 1]], 8
    outs: list[list[int]] = [[] for _ in prompts]

    def one(i):
        outs[i] = list(eng.generate(prompts[i], m))

    eng.start()
    eng.reset_window()
    try:
        ts = [threading.Thread(target=one, args=(i,))
              for i in range(len(prompts))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(120)
        rep = eng.report()
    finally:
        eng.shutdown()
    assert outs == [pipeline_decode_reference(g, params, p, m)
                    for p in prompts]
    head, tail = (pn["totals"] for pn in rep.per_node)
    handed = len(prompts) * (1 + (m - 1) + 1)       # open, steps, close
    assert (head["resident"], head["encodes"]) == (handed, 0)
    assert (tail["resident"], tail["encodes"]) == (0, handed)
    assert head["d2h_bytes"] == 0
    # the tail uploads a wave's positions (4 bytes a padded row) and no
    # activations: a row of them alone is 64 bytes
    assert 0 < tail["h2d_bytes"] <= 4 * 4 * tail["apply_n"]


def _node(max_batch=4, tail=False) -> ComputeNode:
    node = ComputeNode(1, WireCodec("raw", "none"), max_batch=max_batch)
    node._is_tail = tail
    return node


def test_step_rows_of_one_wave_in_order_take_no_op():
    node = _node()
    y = jnp.arange(4 * 3, dtype=jnp.float32).reshape(4, 1, 3)
    tree = {"h": y}
    rows = [ResidentRows(tree, i, 1, y) for i in range(3)]
    xs, up = node._assemble_steps(rows, 4)
    assert xs is y and up == 0


def test_step_rows_of_several_waves_gather_in_one_op():
    """Rows of two upstream waves, out of order, padded to a power of two
    by repeating the last row: one gather, equal to the host path."""
    node = _node(max_batch=8)
    a = jnp.arange(2 * 3, dtype=jnp.float32).reshape(2, 1, 3)
    b = 100 + jnp.arange(4 * 3, dtype=jnp.float32).reshape(4, 1, 3)
    wide = lambda y: jnp.pad(y, [(0, 8 - y.shape[0]), (0, 0), (0, 0)])
    rows = [ResidentRows({"h": b}, 2, 1, wide(b)),
            ResidentRows({"h": a}, 0, 1, wide(a)),
            ResidentRows({"h": b}, 0, 1, wide(b))]
    xs, up = node._assemble_steps(rows, 4)
    want = np.concatenate([np.asarray(b)[2:3], np.asarray(a)[0:1],
                           np.asarray(b)[0:1], np.asarray(b)[0:1]])
    np.testing.assert_array_equal(np.asarray(xs), want)
    assert isinstance(xs, jax.Array) and up == 0
    # encoded rows go through the host, with the same padding
    host = [np.asarray(r.out["h"])[r.start:r.start + 1] for r in rows]
    xs, up = node._assemble_steps(host, 4)
    np.testing.assert_array_equal(np.asarray(xs), want)
    assert up == want.nbytes


def test_oneshot_segments_assemble_like_the_host_path():
    """A whole upstream bucket takes no op; several upstream buckets are
    stacked on the host, equal to stacking the rows and zero-padding."""
    node = _node()
    y = {"h": jnp.arange(4 * D, dtype=jnp.float32).reshape(4, D)}
    ext = [RowExtent(0, 0, 0, 3)]
    whole = _Decoded(ext, y, src=ResidentRows(y, 0, 3))
    stacked, up = node._assemble([whole], 3, 4)
    assert stacked is y and up == 0
    two = [_Decoded(ext, y, src=ResidentRows(y, 0, 3)),
           _Decoded(ext, y, src=ResidentRows(y, 1, 2))]
    stacked, up = node._assemble(two, 5, 8)
    want = np.concatenate([np.asarray(y["h"])[[0, 1, 2, 1, 2]],
                           np.zeros((3, D), np.float32)])
    np.testing.assert_array_equal(np.asarray(stacked["h"]), want)
    assert up == want.nbytes


def test_a_resident_segment_pads_its_middle_axes_on_the_device():
    """pow2 shape buckets: a resident segment's middle axes pad on the
    device to exactly what the host path pads."""
    node = _node()
    y = jnp.arange(2 * 5 * 3, dtype=jnp.float32).reshape(2, 5, 3)
    ext = [RowExtent(0, 0, 0, 2)]
    res = node._pad_to_bucket(_Decoded(ext, {"h": y},
                                       src=ResidentRows({"h": y}, 0, 2)))
    host = node._pad_to_bucket(_Decoded(ext, {"h": np.asarray(y)}))
    assert isinstance(res.src.out["h"], jax.Array)
    assert res.src.out is res.boundary
    np.testing.assert_array_equal(np.asarray(res.boundary["h"]),
                                  host.boundary["h"])
    assert res.extents == host.extents and res.extents[0].pad_trim == (5,)


def test_frame_refuses_a_resident_envelope():
    env = BatchEnvelope([RowExtent(0, 0, 0, 1)], b"",
                        resident=ResidentRows({"h": jnp.zeros((1, D))}))
    with pytest.raises(WireFormatError, match="resident"):
        frame(env)
    ch = get_transport("tcp").channel(2)
    try:
        with pytest.raises(WireFormatError):
            ch.send(env)
    finally:
        ch.close()


def test_a_worker_replica_keeps_the_hop_encoded():
    """The rule as the dispatcher applies it: one replica of the next
    stage that is not an in-process ComputeNode (a worker's handle)
    turns the upstream stage's resident hand-off off."""
    class Handle:                   # a worker's stand-in: no ComputeNode
        retiring = False

    g = mlp_graph(6)
    eng = InferenceEngine(g, 3, RAW)
    d = eng.dispatcher
    flags = lambda: [g.replicas[0].resident_out for g in d.stages]
    try:
        assert flags() == [True, True, False]
        d.stages[1].replicas.append(Handle())
        d._wire_resident()
        assert flags() == [False, True, False]
        d.stages[1].replicas.pop()
        d._wire_resident()
        assert flags() == [True, True, False]
    finally:
        eng.shutdown()


def _stream(eng, n_clients, per_client, base):
    results: dict[int, list] = {}
    errors: list = []

    def client(c):
        try:
            xs = [sample(base + 100 * c + i) for i in range(per_client)]
            results[c] = list(eng.submit_stream(xs, client_id=c))
        except Exception as e:              # pragma: no cover
            errors.append(e)

    ts = [threading.Thread(target=client, args=(c,))
          for c in range(n_clients)]
    for t in ts:
        t.start()
    return ts, results, errors


def _check(g, params, results, errors, n_clients, per_client, base):
    assert not errors, errors
    for c in range(n_clients):
        assert len(results[c]) == per_client    # none lost or duplicated
        for i, got in enumerate(results[c]):    # none reordered
            ref = np.asarray(g.apply(params, jnp.asarray(
                sample(base + 100 * c + i))))
            np.testing.assert_allclose(got, ref, atol=1e-5)


def test_fences_with_resident_envelopes_in_flight():
    """Clients stream through a 3-stage chain while stage 1 grows by a
    replica whose inbox is a tcp channel (stage 0 must encode from the
    fence on), shrinks back (resident again once it drained) and the
    cuts move: nothing lost, reordered or duplicated, numerics as the
    reference's."""
    g = mlp_graph(8)
    params = g.init(jax.random.PRNGKey(0))

    def tcp_joiner(d, stage, replica):
        if replica == 0:
            return None
        node = d._make_node(stage, replica)
        node.inbox = d._open_channel("tcp", 8)
        return node

    eng = InferenceEngine(g, TopologySpec.chain(g, 3), RAW, max_batch=2,
                          replica_factory=tcp_joiner)
    eng.configure(params)
    d = eng.dispatcher
    head = d.stages[0].replicas[0]
    eng.start()
    try:
        runs = []
        for base, change in ((0, lambda: eng.scale(1, 2)),
                             (3000, lambda: eng.scale(1, 1)),
                             (6000, lambda: d.reconfigure((2, 5)))):
            ts, results, errors = _stream(eng, 3, 12, base)
            time.sleep(0.02)
            rec = change()
            for t in ts:
                t.join(120)
            runs.append((base, results, errors, rec, head.resident_out))
            assert rec["changed"] and rec["acknowledged"]
        ts, results, errors = _stream(eng, 3, 8, 9000)
        for t in ts:
            t.join(120)
        runs.append((9000, results, errors, None, head.resident_out))
        resident = head.snapshot()["resident"]
    finally:
        eng.shutdown()
    for base, results, errors, _, _ in runs:
        _check(g, params, results, errors, 3, len(results[0]), base)
    # a tcp member behind stage 1: encoded; it drained: resident again
    assert [r[4] for r in runs] == [False, True, True, True]
    assert resident > 0


def test_an_error_and_session_lost_still_reach_the_client():
    """An application error raised inside stage 0's call on a resident hop
    fails exactly its request; a session whose slot is gone at stage 1
    (its rows arrive there resident) raises SessionLost at the client;
    the chain keeps serving."""
    from tests._worker_graphs import POISON
    g = poison_graph()
    params = g.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(g, 2, RAW)
    eng.configure(params)
    eng.start()
    try:
        bad = np.full((1, D), POISON, np.float32)
        with pytest.raises(NodeError, match="poison"):
            eng.submit(bad).result(timeout=60)
        ok = sample(1)
        ref = np.asarray(g.apply(params, jnp.asarray(ok)))
        np.testing.assert_allclose(eng.submit(ok).result(timeout=60), ref,
                                   atol=1e-5)
    finally:
        eng.shutdown()

    g = lm_graph()
    params = g.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(g, TopologySpec.chain(g, 2), RAW, max_batch=4)
    eng.configure(params)
    eng.start()
    try:
        gen = eng.generate([1, 5, 9, 2], 6, session_id="s", restart="never")
        next(gen)
        tail = eng.dispatcher.stages[1].replicas[0]
        assert eng.dispatcher.stages[0].replicas[0].resident_out
        tail.sessions.pop("s")              # stage 1 lost the slot
        with pytest.raises(SessionLost):
            next(gen)
        other = list(eng.generate([3, 3, 7], 4))
        assert other == pipeline_decode_reference(g, params, [3, 3, 7], 4)
    finally:
        eng.shutdown()
