"""Transport conformance + wire-path hardening.

The conformance suite runs the SAME contract over every registered
backend — in-proc queues, real TCP loopback sockets, and the emulated
CORE-style link — because the runtime's ordering and flow-control
arguments (epoch fences, staged-relay backpressure, `_STOP` accounting)
assume nothing about a channel beyond FIFO delivery, bounded in-flight
items, and token identity.  The hardening tests prove the failure story:
a truncated blob or a killed socket fails exactly the affected batch as a
NodeError while the chain keeps serving and shuts down cleanly.
"""
import dataclasses
import queue
import threading
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.runtime.wire as wire_mod
from repro.core.graph import LayerGraph
from repro.core.metrics import EDGE
from repro.runtime import InferenceEngine, StageSpec, TopologySpec
from repro.runtime.dispatcher import DispatcherCodecs, NodeError
from repro.runtime.transport import (ChannelClosed, InprocTransport,
                                     LinkTransport, TcpChannel,
                                     _TRANSPORTS, get_transport,
                                     register_transport)
from repro.runtime.wire import (BatchEnvelope, NodePlan, ReconfigMarker,
                                RowExtent, WireCodec, WireFormatError,
                                _RETIRE, _STOP, frame, slice_parts, unframe)

D = 16

RAW = DispatcherCodecs(data=WireCodec("raw", "none"),
                       weights=WireCodec("raw", "none"))

# every registered backend plus a parameterized link (jitter on, to prove
# the monotonic-ready clamp keeps FIFO); new register_transport backends
# are picked up automatically
BACKENDS = sorted(_TRANSPORTS) + ["link:40mbit,1ms,0.5ms"]


def envelope(i: int, cid=0, rows: int = 1, blob: bytes = b"x" * 32,
             epoch: int = 0) -> BatchEnvelope:
    return BatchEnvelope([RowExtent(i, cid, i, rows, t_submit=0.25)],
                         blob, epoch=epoch)


def mlp_graph(depth: int = 6, d: int = D) -> LayerGraph:
    g = LayerGraph("toy-mlp", jax.ShapeDtypeStruct((1, d), np.float32))
    prev = ""
    for i in range(depth):
        g.layer(f"fc{i}",
                lambda p, x: jnp.tanh(x @ p["w"]),
                {"w": jax.ShapeDtypeStruct((d, d), np.float32)},
                (prev,),
                jax.ShapeDtypeStruct((1, d), np.float32),
                flops=2.0 * d * d)
        prev = f"fc{i}"
    return g


def sample(i: int) -> np.ndarray:
    return np.random.default_rng(i).normal(size=(1, D)).astype(np.float32)


def make_engine(topology, graph=None, **kw):
    g = graph if graph is not None else mlp_graph()
    params = g.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(g, topology, RAW, **kw)
    eng.configure(params)
    return g, params, eng


def shutdown_or_fail(eng, timeout=60.0):
    """Shutdown on a watchdog: a hang here is the bug being tested for."""
    t = threading.Thread(target=eng.shutdown, daemon=True)
    t.start()
    t.join(timeout)
    assert not t.is_alive(), "engine shutdown hung"


# -- frame()/unframe(): the byte wire under every transport -------------------

def test_frame_roundtrip_envelope():
    env = BatchEnvelope(
        [RowExtent(7, ("bg", 3), 2, 4, t_submit=1.25, pad_trim=(3, 5),
                   attempt=2),
         RowExtent(8, "client-x", 0, 1),
         RowExtent(9, 0, 1, 2)],
        b"\x00\x01payload\xff", epoch=3)
    r = unframe(frame(env))
    assert r.epoch == 3 and r.blob == env.blob and r.error is None
    assert r.extents[0].client_id == ("bg", 3)
    assert isinstance(r.extents[0].client_id, tuple)    # hashable again
    assert r.extents[0].pad_trim == (3, 5)
    assert r.extents[0].t_submit == 1.25                # exact (f64)
    assert r.extents[0].attempt == 2                    # replay tag rides
    assert r.extents[1].client_id == "client-x"
    assert r.extents[1].pad_trim is None
    assert r.extents[1].attempt == 0
    assert r.retryable is False
    err = unframe(frame(BatchEnvelope([RowExtent(1, 0, 0, 1)], b"",
                                      error="trace\nback ü", epoch=1,
                                      retryable=True)))
    assert err.error == "trace\nback ü" and err.blob == b""
    assert err.retryable is True                # classification rides too


def test_frame_roundtrip_tokens_and_marker():
    assert unframe(frame(_STOP)) is _STOP       # the very same singleton
    assert unframe(frame(_RETIRE)) is _RETIRE
    codec = WireCodec("zfp", "lz4", zfp_rate=12, vectorized=False)
    m = ReconfigMarker(4, {
        1: NodePlan(2, 5, b'{"layers": []}', b"WWWW",
                    WireCodec("raw", "none"), wire_bytes=18),
        0: NodePlan(0, 2, b"a", b"", codec, wire_bytes=1)})
    r = unframe(frame(m))
    assert r.epoch == 4 and sorted(r.plans) == [0, 1]
    assert r.plans[1].weights_blob == b"WWWW" and r.plans[1].lo == 2
    assert r.plans[0].weights_codec == codec
    # empty marker (scale fences carry no plans)
    assert unframe(frame(ReconfigMarker(9, {}))).plans == {}


def test_frame_rejects_non_channel_items():
    with pytest.raises(WireFormatError):
        frame(object())
    with pytest.raises(WireFormatError):        # unencodable client id
        frame(envelope(0, cid=object()))


def test_unframe_truncation_is_always_wireformaterror():
    blob = frame(envelope(3, cid=("a", 1), blob=b"b" * 100))
    for k in range(len(blob)):
        with pytest.raises(WireFormatError):
            unframe(blob[:k])
    with pytest.raises(WireFormatError):        # trailing garbage
        unframe(blob + b"!")


def test_unframe_corruption_fuzz():
    """Flipped bytes either parse (flip landed in the payload) or raise
    WireFormatError — never a bare struct.error/ValueError/KeyError.  The
    seed carries the v3 reliability fields so flips land in the attempt
    header and the flags byte too."""
    blob = frame(BatchEnvelope(
        [RowExtent(3, ("a", 1), 3, 1, t_submit=0.25, attempt=1)],
        b"b" * 64, error="boom", retryable=True))
    rng = np.random.default_rng(0)
    for _ in range(300):
        b = bytearray(blob)
        for _ in range(int(rng.integers(1, 4))):
            b[int(rng.integers(len(b)))] = int(rng.integers(256))
        try:
            unframe(bytes(b))
        except WireFormatError:
            pass


def test_old_frame_version_rejected_by_name_compat_path_decodes():
    """FRAME_VERSION bumped to 4 (decode-session fields): old frames are
    refused by the strict decoder with an error NAMING the versions, the
    explicit compat path still decodes v2/v3 (missing fields at their
    defaults), and newer-only field values refuse to frame as an older
    version rather than silently dropping the tag."""
    from repro.runtime.wire import FRAME_VERSION, unframe_compat
    assert FRAME_VERSION == 4
    env = BatchEnvelope([RowExtent(7, "c", 2, 4, t_submit=1.25)],
                        b"payload", epoch=2)
    for old_v in (2, 3):
        old = frame(env, version=old_v)
        with pytest.raises(WireFormatError,
                           match=rf"version {old_v}.*speaking 4"):
            unframe(old)
        r = unframe_compat(old)
        assert r.blob == b"payload" and r.extents[0].request_id == 7
        assert r.extents[0].attempt == 0 and r.retryable is False
        assert r.extents[0].session is None
        assert r.extents[0].kind == 0 and r.extents[0].pos == 0
    # current frames flow through the compat path too
    r4 = unframe_compat(frame(env))
    assert r4.extents[0].t_submit == 1.25
    # v3-only values are not representable in v2
    with pytest.raises(WireFormatError, match="attempt"):
        frame(BatchEnvelope([RowExtent(1, 0, 0, 1, attempt=1)], b""),
              version=2)
    with pytest.raises(WireFormatError, match="retryable"):
        frame(BatchEnvelope([RowExtent(1, 0, 0, 1)], b"", error="e",
                            retryable=True), version=2)
    # v4-only values (decode sessions) are not representable in v3
    with pytest.raises(WireFormatError, match="session"):
        frame(BatchEnvelope([RowExtent(1, 0, 0, 1, session="s", kind=2,
                                       pos=5)], b""), version=3)


# -- decode_tree / decode_array: untrusted blobs ------------------------------

def test_decode_tree_truncated_blob():
    wc = WireCodec("raw", "none")
    blob, _ = wc.encode_tree(
        {"a": np.arange(12, dtype=np.float32).reshape(3, 4),
         "b": np.ones((2, 2), np.float32)}, "data")
    out, _ = wc.decode_tree(blob)
    assert set(out) == {"a", "b"}
    for cut in (0, 2, 4, 9, len(blob) // 2, len(blob) - 1):
        with pytest.raises(WireFormatError):
            wc.decode_tree(blob[:cut])
    with pytest.raises(WireFormatError):
        wc.decode_tree(blob + b"xx")
    # corrupt leaf count cannot allocate-loop its way to a struct.error
    with pytest.raises(WireFormatError):
        wc.decode_tree(b"\xff\xff\xff\x7f" + blob[4:])
    # regression: truncation landing BETWEEN leaves (the 2-leaf count
    # header still passes the up-front guard, leaf 0 parses whole, and
    # leaf 1's 4-byte name-length header is short) must be
    # WireFormatError, not a bare struct.error from the header unpack
    solo, _ = wc.encode_tree(
        {"a": np.arange(12, dtype=np.float32).reshape(3, 4)}, "data")
    leaf0_end = len(solo)                       # leaf 0 bytes == solo[4:]
    two_leaves_cut = blob[:leaf0_end + 2]       # 2 stray header bytes
    with pytest.raises(WireFormatError):
        wc.decode_tree(two_leaves_cut)


@pytest.mark.parametrize("codec", [WireCodec("raw", "none"),
                                   WireCodec("zfp", "lz4", zfp_rate=16),
                                   WireCodec("q8", "none"),
                                   WireCodec("json", "none")])
def test_decode_array_corrupt_blob(codec):
    blob = codec.encode_array(np.ones((4, 8), np.float32))
    codec.decode_array(blob)                    # intact: fine
    for cut in (0, 1, len(blob) // 3, len(blob) - 1):
        try:
            codec.decode_array(blob[:cut])
        except WireFormatError:
            pass        # the contract: WireFormatError or a clean parse


def test_truncated_blob_fails_only_the_affected_batch():
    """Regression (ISSUE 5): a corrupt wire payload mid-chain — now
    reachable via a dropped socket — must fail exactly the affected batch
    with NodeError and leave the chain serving.  The hop frames (tcp), so
    the head's codec runs on it."""
    class TruncatingCodec:
        def __init__(self, inner):
            self._inner = inner
            self.arm = 0

        def encode_tree(self, *a, **kw):
            blob, rec = self._inner.encode_tree(*a, **kw)
            if self.arm:
                self.arm -= 1
                blob = blob[: len(blob) // 2]
            return blob, rec

        def __getattr__(self, name):
            return getattr(self._inner, name)

    g, params, eng = make_engine(
        TopologySpec.chain(mlp_graph(), 2, transport="tcp"), max_batch=1)
    eng.start()
    node0 = eng.dispatcher.stages[0].replicas[0]
    node0.data_codec = TruncatingCodec(node0.data_codec)
    assert eng.submit(sample(0)).result(timeout=60) is not None

    node0.data_codec.arm = 1                    # corrupt the next payload
    with pytest.raises(NodeError, match="WireFormatError"):
        eng.submit(sample(1)).result(timeout=60)

    ref = np.asarray(g.apply(params, jnp.asarray(sample(2))))
    np.testing.assert_allclose(eng.submit(sample(2)).result(timeout=60),
                               ref, atol=1e-5)  # chain kept serving
    shutdown_or_fail(eng)


# -- conformance: same contract over every backend ----------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_conformance_fifo(backend):
    ch = get_transport(backend).channel(0)
    try:
        for i in range(40):
            ch.send(envelope(i))
        got = [ch.recv(timeout=10).extents[0].request_id for _ in range(40)]
        assert got == list(range(40))
        with pytest.raises(queue.Empty):
            ch.recv(timeout=0.02)
        with pytest.raises(queue.Empty):
            ch.recv_nowait()
    finally:
        ch.close()


@pytest.mark.parametrize("backend", BACKENDS)
def test_conformance_tokens(backend):
    """Stop / retire / fence markers round-trip with identity preserved —
    the routers' `is _STOP` checks and epoch accounting must work on the
    far side of any backend."""
    ch = get_transport(backend).channel(0)
    try:
        plan = NodePlan(1, 3, b'{"layers": ["fc1", "fc2"]}', b"wts",
                        WireCodec("raw", "none"), wire_bytes=30)
        ch.send(envelope(0, epoch=2))
        ch.send(ReconfigMarker(3, {0: plan}))
        ch.send(_STOP)
        ch.send(_RETIRE)
        env = ch.recv(timeout=10)
        assert env.epoch == 2 and env.extents[0].request_id == 0
        m = ch.recv(timeout=10)
        assert m.epoch == 3 and m.plans[0].arch_blob == plan.arch_blob
        assert ch.recv(timeout=10) is _STOP
        assert ch.recv(timeout=10) is _RETIRE
    finally:
        ch.close()


@pytest.mark.parametrize("backend", BACKENDS)
def test_conformance_backpressure_and_qsize(backend):
    """A capacity-k channel admits at most k unconsumed sends (the
    staged-relay flow-control contract: kernel socket buffers must not
    widen the window), and qsize reports the outstanding depth lqd
    routing keys on."""
    cap = 4
    ch = get_transport(backend).channel(cap)
    try:
        sent = []

        def sender():
            for i in range(cap * 2):
                ch.send(envelope(100 + i))
                sent.append(i)

        t = threading.Thread(target=sender, daemon=True)
        t.start()
        time.sleep(0.6)
        assert len(sent) <= cap, f"backpressure leak: {len(sent)} > {cap}"
        assert ch.qsize() >= cap - 1            # the depth signal is live
        for _ in range(cap * 2):
            ch.recv(timeout=10)
        t.join(10)
        assert not t.is_alive()
        deadline = time.monotonic() + 5
        while ch.qsize() != 0 and time.monotonic() < deadline:
            time.sleep(0.01)                    # credits return async
        assert ch.qsize() == 0
    finally:
        ch.close()


@pytest.mark.parametrize("backend", BACKENDS)
def test_conformance_engine_end_to_end(backend):
    """A replicated chain serves correct, per-client-FIFO results over
    the backend, survives a live scale-down, and shuts down cleanly."""
    spec = TopologySpec.chain(mlp_graph(), 2,
                              transport=backend).with_replicas(0, 2)
    g, params, eng = make_engine(spec, max_batch=2)
    eng.start()
    futs = [eng.submit(sample(i), client_id=("c", i % 3)) for i in range(10)]
    for i, f in enumerate(futs):
        ref = np.asarray(g.apply(params, jnp.asarray(sample(i))))
        np.testing.assert_allclose(f.result(timeout=60), ref, atol=1e-5)
    rec = eng.scale(0, 1)                       # drain a replica live
    assert rec["changed"] and rec["acknowledged"]
    for i in range(10, 14):
        ref = np.asarray(g.apply(params, jnp.asarray(sample(i))))
        np.testing.assert_allclose(
            eng.submit(sample(i)).result(timeout=60), ref, atol=1e-5)
    shutdown_or_fail(eng)


def test_mixed_transports_per_stage():
    """Each stage binds its own backend (the per-stage transport config
    from the ISSUE): tcp into stage 0, an emulated link into stage 1,
    in-proc at the tail."""
    g = mlp_graph()
    spec = TopologySpec((
        StageSpec((0, 2), transport="tcp"),
        StageSpec((2, 4), transport="link:80mbit,1ms"),
        StageSpec((4, 6), transport="inproc"),
    ))
    g, params, eng = make_engine(spec, graph=g, max_batch=2)
    eng.start()
    for i in range(6):
        ref = np.asarray(g.apply(params, jnp.asarray(sample(i))))
        np.testing.assert_allclose(
            eng.submit(sample(i)).result(timeout=60), ref, atol=1e-5)
    shutdown_or_fail(eng)


def test_link_shaping_delays_delivery():
    """The emulated link is actually shaped: a 10 KB frame over 1 mbit
    takes >= 80 ms to become receivable."""
    ch = get_transport("link:1mbit,0ms").channel(0)
    try:
        t0 = time.monotonic()
        ch.send(envelope(0, blob=b"z" * 10_000))
        ch.recv(timeout=10)
        assert time.monotonic() - t0 >= 0.07
    finally:
        ch.close()


def test_link_spec_parsing():
    tr = LinkTransport.from_spec("10mbit,20ms,5ms")
    assert tr.bandwidth_bytes_s == pytest.approx(1.25e6)
    assert tr.latency_s == pytest.approx(0.020)
    assert tr.jitter_s == pytest.approx(0.005)
    assert LinkTransport.from_spec("1gbit,2ms").jitter_s == 0.0
    with pytest.raises(ValueError):
        LinkTransport.from_spec("10parsecs,20ms")
    with pytest.raises(ValueError):
        LinkTransport.from_spec("10mbit,20ms,1ms,oops")
    with pytest.raises(ValueError, match="unknown transport"):
        get_transport("warp:9")


# -- kill the socket: the chain survives a dead replica link ------------------

def test_tcp_kill_fails_batch_chain_keeps_serving():
    """Sever one replica's TCP inbox mid-serve: any batch already routed
    onto the dead link fails with NodeError (never a hang, never a wrong
    answer), the router heals onto the sibling — since ISSUE 7 it also
    PROBES channel liveness, so a link severed while no send is in
    flight is healed before another batch is risked on it — later
    requests succeed, and shutdown still joins every thread (the router
    proxies the dead replica's fence/stop tokens downstream)."""
    spec = TopologySpec.chain(mlp_graph(), 1,
                              transport="tcp").with_replicas(0, 2)
    g, params, eng = make_engine(spec, max_batch=1)
    eng.start()
    for i in range(4):                          # both replicas warm
        eng.submit(sample(i)).result(timeout=60)

    victim = eng.dispatcher.stages[0].replicas[1]
    assert isinstance(victim.inbox, TcpChannel)
    victim.inbox.kill()

    outcomes = []
    for i in range(8):
        try:
            res = eng.submit(sample(10 + i)).result(timeout=60)
            ref = np.asarray(g.apply(params, jnp.asarray(sample(10 + i))))
            np.testing.assert_allclose(res, ref, atol=1e-5)
            outcomes.append("ok")
        except NodeError:
            outcomes.append("failed")
    # only batches the router had already risked on the dead link may
    # fail (at most the one in flight — liveness probing heals the
    # member otherwise); traffic recovered and kept succeeding
    assert outcomes.count("failed") <= 1, outcomes
    assert outcomes[-1] == "ok" and outcomes.count("ok") >= 4, outcomes
    # the dead replica self-retired off the live set
    deadline = time.monotonic() + 20
    while (len(eng.dispatcher.stages[0].live_replicas()) > 1
           and time.monotonic() < deadline):
        time.sleep(0.05)
    assert len(eng.dispatcher.stages[0].live_replicas()) == 1
    shutdown_or_fail(eng)


def test_unencodable_client_id_rejected_at_submit():
    """A client id the byte framing cannot carry is a clear submit-time
    error on ANY topology — not a silent mid-chain relay failure on
    whichever stage binds a socket transport."""
    _, _, eng = make_engine(TopologySpec.chain(mlp_graph(), 2),
                            max_batch=1)
    eng.start()
    with pytest.raises(WireFormatError, match="not wire-encodable"):
        eng.submit(sample(0), client_id=frozenset({1}))
    # tuple/str/int ids stay fine, and the rejection left no debris
    eng.submit(sample(1), client_id=("ok", 1)).result(timeout=60)
    shutdown_or_fail(eng)


def test_tcp_kill_under_load_every_future_resolves():
    """Kill a replica's inbox with batches genuinely in flight: whatever
    was stranded in the dead link's buffers fails via the router's
    in-flight ledger — every future resolves (result or NodeError),
    none hangs."""
    spec = TopologySpec.chain(mlp_graph(), 1,
                              transport="tcp").with_replicas(0, 2)
    g, params, eng = make_engine(spec, max_batch=1, queue_depth=4)
    eng.start()
    for i in range(4):
        eng.submit(sample(i)).result(timeout=60)

    futs = [eng.submit(sample(20 + i), client_id=i % 3) for i in range(16)]
    eng.dispatcher.stages[0].replicas[1].inbox.kill()
    outcomes = {"ok": 0, "failed": 0}
    for f in futs:
        try:
            f.result(timeout=60)
            outcomes["ok"] += 1
        except NodeError:
            outcomes["failed"] += 1
    assert outcomes["ok"] >= 1, outcomes      # the chain kept serving
    # and the healed chain still serves fresh traffic
    eng.submit(sample(99)).result(timeout=60)
    shutdown_or_fail(eng)


def test_tcp_dead_tail_fails_pending_not_hangs():
    """Sever the collector's result channel: in-flight futures fail with
    NodeError instead of hanging, new submits are refused with a clear
    error, and shutdown completes."""
    spec = TopologySpec.chain(mlp_graph(), 1, transport="tcp")
    g, params, eng = make_engine(spec, max_batch=1)
    eng.start()
    eng.submit(sample(0)).result(timeout=60)

    futs = [eng.submit(sample(1 + i)) for i in range(4)]
    eng.dispatcher.result_channel.kill()
    for f in futs:                  # resolve — completed or failed — fast
        try:
            f.result(timeout=30)
        except NodeError:
            pass
    deadline = time.monotonic() + 20
    while not eng.dispatcher._tail_dead and time.monotonic() < deadline:
        time.sleep(0.02)
    assert eng.dispatcher._tail_dead
    with pytest.raises(RuntimeError, match="no longer deliver"):
        eng.submit(sample(50))
    shutdown_or_fail(eng)


def test_tcp_dead_midchain_link_fails_pending_not_hangs():
    """Sever a MID-chain stage-input link: the dead stage's router stops
    the chain downstream, the collector recognizes the stop cascade it
    did not initiate and fails everything unresolved, new submits are
    refused, and shutdown completes — the generalization of the dead-tail
    case one hop earlier."""
    spec = TopologySpec.chain(mlp_graph(), 3, transport="tcp")
    g, params, eng = make_engine(spec, max_batch=1)
    eng.start()
    eng.submit(sample(0)).result(timeout=60)

    futs = [eng.submit(sample(1 + i)) for i in range(6)]
    eng.dispatcher._stage_inputs[1].kill()      # stage 1's inbound link
    for f in futs:                  # resolve — completed or failed — fast
        try:
            f.result(timeout=30)
        except NodeError:
            pass
    deadline = time.monotonic() + 20
    while not eng.dispatcher._tail_dead and time.monotonic() < deadline:
        time.sleep(0.02)
    assert eng.dispatcher._tail_dead
    with pytest.raises(RuntimeError, match="no longer deliver"):
        eng.submit(sample(50))
    shutdown_or_fail(eng)


def _generous_policy():
    from repro.runtime.dispatcher import RetryPolicy
    return RetryPolicy(max_attempts=5, backoff_s=0.02,
                       retry_budget=64.0, refill_per_s=32.0)


def test_tcp_kill_with_retry_policy_zero_failures():
    """The same dead-link drill as above, with a retry policy: stranded
    batches are re-admitted through the healed routing set instead of
    failing — EVERY future resolves with the correct result, no client
    ever sees a NodeError."""
    spec = TopologySpec.chain(mlp_graph(), 1,
                              transport="tcp").with_replicas(0, 2)
    g, params, eng = make_engine(spec, max_batch=1, queue_depth=4,
                                 retry_policy=_generous_policy())
    eng.start()
    for i in range(4):                          # both replicas warm
        eng.submit(sample(i)).result(timeout=60)

    futs = [(20 + i, eng.submit(sample(20 + i), client_id=i % 3))
            for i in range(16)]
    eng.dispatcher.stages[0].replicas[1].inbox.kill()
    for i, f in futs:       # no try/except: a NodeError IS the failure
        ref = np.asarray(g.apply(params, jnp.asarray(sample(i))))
        np.testing.assert_allclose(f.result(timeout=60), ref, atol=1e-5)
    # the healed chain serves fresh traffic on the survivor
    eng.submit(sample(99)).result(timeout=60)
    shutdown_or_fail(eng)


def test_tcp_dead_tail_revives_and_replays_with_retry_policy():
    """The un-bricking path: severing the result channel used to poison
    the dispatcher forever (_tail_dead, 'restart the engine').  With a
    retry policy the collector rebuilds the tail channel in place,
    replays what was in flight, and keeps accepting submits — zero
    client-visible failures."""
    spec = TopologySpec.chain(mlp_graph(), 1, transport="tcp")
    g, params, eng = make_engine(spec, max_batch=1,
                                 retry_policy=_generous_policy())
    eng.start()
    eng.submit(sample(0)).result(timeout=60)

    futs = [(1 + i, eng.submit(sample(1 + i))) for i in range(4)]
    eng.dispatcher.result_channel.kill()
    for i, f in futs:
        ref = np.asarray(g.apply(params, jnp.asarray(sample(i))))
        np.testing.assert_allclose(f.result(timeout=60), ref, atol=1e-5)
    assert eng.dispatcher.replay_stats.tail_revives >= 1
    assert not eng.dispatcher._tail_dead
    # new submits are NOT refused — the engine needed no restart
    eng.submit(sample(50)).result(timeout=60)
    shutdown_or_fail(eng)


def test_tcp_dead_tail_still_fails_fast_without_policy():
    """Replay OFF must preserve the PR 7 contract byte-for-byte: this is
    test_tcp_dead_tail_fails_pending_not_hangs re-asserted next to its
    replay twin so the two semantics are diffable side by side."""
    spec = TopologySpec.chain(mlp_graph(), 1, transport="tcp")
    g, params, eng = make_engine(spec, max_batch=1)
    eng.start()
    eng.submit(sample(0)).result(timeout=60)
    fut = eng.submit(sample(1))
    eng.dispatcher.result_channel.kill()
    try:
        fut.result(timeout=30)
    except NodeError:
        pass
    deadline = time.monotonic() + 20
    while not eng.dispatcher._tail_dead and time.monotonic() < deadline:
        time.sleep(0.02)
    assert eng.dispatcher._tail_dead
    assert eng.dispatcher.replay_stats.tail_revives == 0
    with pytest.raises(RuntimeError, match="no longer deliver"):
        eng.submit(sample(50))
    shutdown_or_fail(eng)


# -- registry: re-registration vs live channels -------------------------------

def test_register_transport_refuses_while_channels_live():
    register_transport("reg-probe", InprocTransport)
    tr = get_transport("reg-probe")
    ch = tr.channel(1)
    with pytest.raises(ValueError, match="live"):
        register_transport("reg-probe", InprocTransport)
    assert get_transport("reg-probe") is tr     # instance NOT stranded
    ch.close()
    register_transport("reg-probe", InprocTransport)    # idle now: fine
    assert get_transport("reg-probe") is not tr

    ch2 = get_transport("reg-probe").channel(1)
    register_transport("reg-probe", InprocTransport, force=True)
    ch2.close()
    del _TRANSPORTS["reg-probe"]


def test_register_transport_scheme_strand_protection():
    from repro.runtime.transport import (_INSTANCES, _SCHEMES,
                                         register_transport_scheme)
    register_transport_scheme("probe-sch", lambda args: InprocTransport())
    tr = get_transport("probe-sch:x")
    ch = tr.channel(1)
    with pytest.raises(ValueError, match="live"):
        register_transport_scheme("probe-sch",
                                  lambda args: InprocTransport())
    assert get_transport("probe-sch:x") is tr   # not stranded
    ch.close()
    register_transport_scheme("probe-sch", lambda args: InprocTransport())
    # stale cached instances dropped: the new factory actually serves
    assert get_transport("probe-sch:x") is not tr
    del _SCHEMES["probe-sch"]
    _INSTANCES.pop("probe-sch:x", None)


def test_engine_shutdown_releases_channels():
    register_transport("reg-engine", InprocTransport)
    spec = TopologySpec.chain(mlp_graph(), 2, transport="reg-engine")
    _, _, eng = make_engine(spec, max_batch=2)
    eng.start()
    eng.submit(sample(0)).result(timeout=60)
    tr = get_transport("reg-engine")
    assert tr.live_channels > 0
    with pytest.raises(ValueError, match="live"):
        register_transport("reg-engine", InprocTransport)
    shutdown_or_fail(eng)
    assert tr.live_channels == 0                # shutdown closed them all
    register_transport("reg-engine", InprocTransport)
    del _TRANSPORTS["reg-engine"]


# -- slice_parts pad_trim rank mismatch: one-shot warning ---------------------

def test_slice_parts_rank_mismatch_warns_once():
    wire_mod._RANK_MISMATCH_WARNED = False
    flat = {"out": np.ones((4, 7), np.float32)}         # rank 2
    ext = [RowExtent(0, 0, 0, 4, pad_trim=(5,))]        # expects rank 3
    with pytest.warns(RuntimeWarning, match="pad_safe=False"):
        parts = slice_parts(flat, ext)
    assert parts[0]["out"].shape == (4, 7)              # passed through
    with warnings.catch_warnings():
        warnings.simplefilter("error")                  # would raise if
        slice_parts(flat, ext)                          # warned again
    # matching ranks stay silent and still trim
    wire_mod._RANK_MISMATCH_WARNED = False
    flat3 = {"out": np.ones((4, 8, 3), np.float32)}
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        parts = slice_parts(flat3, [RowExtent(0, 0, 0, 4, pad_trim=(5,))])
    assert parts[0]["out"].shape == (4, 5, 3)


# -- replica-aware energy: idle replicas burn the baseline --------------------

def test_engine_idle_energy_accounting():
    # default profile (idle_w = 0): figures unchanged, idle term zero
    spec = TopologySpec.chain(mlp_graph(), 1).with_replicas(0, 2)
    _, _, eng = make_engine(spec, max_batch=2)
    eng.start()
    for i in range(6):
        eng.submit(sample(i)).result(timeout=60)
    rep = eng.report()
    assert all(pn["idle_energy_j"] == 0.0 for pn in rep.per_node)
    # per-cycle total: each replica's per-request energy weighted by the
    # share of the window's cycles it actually served
    active = sum(pn["energy_j"] * pn["requests"] for pn in rep.per_node) \
        / rep.samples
    assert rep.per_node_energy_j == pytest.approx(active / rep.num_nodes)
    # one stage: its replicas' request counts tile the window exactly
    assert sum(pn["requests"] for pn in rep.per_node) == rep.samples
    shutdown_or_fail(eng)

    # idle_w > 0: a mostly-idle replicated stage books baseline burn
    hw = dataclasses.replace(EDGE, idle_w=5.0)
    _, _, eng = make_engine(spec, max_batch=2, hw=hw)
    eng.start()
    for i in range(6):
        eng.submit(sample(i)).result(timeout=60)
    time.sleep(0.3)                             # guaranteed idle window
    rep = eng.report()
    assert all(pn["idle_energy_j"] > 0.0 for pn in rep.per_node)
    active = sum(pn["energy_j"] * pn["requests"] for pn in rep.per_node) \
        / rep.samples
    idle = sum(pn["idle_energy_j"] for pn in rep.per_node)
    assert rep.per_node_energy_j == pytest.approx(
        (active + idle) / rep.num_nodes)
    shutdown_or_fail(eng)


def test_emulator_replicas_energy():
    """emulate(replicas=...): 1-replica formulas reduce to the pre-replica
    report; replicating the bottleneck raises modeled throughput; idle
    replicas burn idle_w."""
    from repro.core.emulator import emulate
    g = mlp_graph(8)
    base = emulate(g, 4, seed=0)
    assert base.replicas == () and base.num_nodes == 4

    ones = emulate(g, 4, seed=0, replicas=[1, 1, 1, 1])
    assert ones.replicas == (1, 1, 1, 1) and ones.num_nodes == 4
    # the 1-replica case is unchanged: no idle term (idle_w=0), the same
    # per-node mean over 4 nodes, the same bottleneck law
    assert all(s.idle_energy_j == 0.0 for s in ones.stages)
    assert ones.per_node_energy_j == pytest.approx(
        sum(s.energy_j for s in ones.stages) / 4)
    assert ones.throughput_cps == pytest.approx(
        1.0 / max(s.service_s for s in ones.stages))

    svc = [s.service_s for s in base.stages]
    reps = [1] * 4
    reps[int(np.argmax(svc))] = 2               # replicate the bottleneck
    r2 = emulate(g, 4, seed=0, replicas=reps)
    assert r2.num_nodes == 5 and sum(r2.replicas) == 5
    # structural (codec timings are measured, so cross-run comparisons are
    # noisy): the bottleneck prices the amortized rate, which can only be
    # at or below the unamortized service time of the same run
    amort = max(s.rate_service_s for s in r2.stages)
    assert r2.throughput_cps == pytest.approx(1.0 / amort)
    assert amort <= max(s.service_s for s in r2.stages)
    rep_stage = r2.stages[int(np.argmax(reps))]
    assert rep_stage.rate_service_s == pytest.approx(
        rep_stage.service_s / 2)

    hw = dataclasses.replace(EDGE, idle_w=3.0)
    r_idle = emulate(g, 4, seed=0, hw=hw, replicas=reps)
    assert sum(s.idle_energy_j for s in r_idle.stages) > 0
    assert r_idle.per_node_energy_j == pytest.approx(
        sum(s.energy_j + s.idle_energy_j for s in r_idle.stages) / 5)
    with pytest.raises(ValueError):
        emulate(g, 4, replicas=[1, 1])          # wrong length
