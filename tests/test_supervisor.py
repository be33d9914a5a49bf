"""Process-per-replica supervision: control-frame wire round-trips,
cross-process channel completion (expect/dial), the half-open-hello
accept guard, worker graph-factory resolution, spawn-failure cleanup,
and a fast end-to-end smoke over real worker processes — the quick legs;
the long chaos drills live in test_chaos.py."""
import os
import socket
import struct
import threading
import time

import jax
import numpy as np
import pytest

from repro.runtime import NodeError, TopologySpec
from repro.runtime.dispatcher import DispatcherCodecs
from repro.runtime.supervisor import (Supervisor, SupervisorConfig,
                                      WorkerRefused, supervised_engine)
from repro.runtime.transport import (ChannelClosed, TcpTransport,
                                     dial_channel, recv_framed, send_framed)
from repro.runtime.wire import (FRAME_VERSION, BatchEnvelope, ControlFrame,
                                RowExtent, WireCodec, frame, unframe)
from repro.runtime.worker import load_graph_factory
from tests._worker_graphs import mlp_graph

GRAPHS = os.path.join(os.path.dirname(__file__), "_worker_graphs.py")
RAW = DispatcherCodecs(data=WireCodec("raw", "none"),
                       weights=WireCodec("raw", "none"))


def _cfg(**kw):
    kw.setdefault("graph_factory", GRAPHS + ":mlp_graph")
    kw.setdefault("heartbeat_s", 0.1)
    kw.setdefault("heartbeat_timeout_s", 5.0)
    kw.setdefault("backoff_initial_s", 0.1)
    kw.setdefault("backoff_max_s", 0.5)
    kw.setdefault("spawn_timeout_s", 60.0)
    kw.setdefault("shutdown_grace_s", 5.0)
    return SupervisorConfig(**kw)


# -- ControlFrame on the wire -------------------------------------------------

def test_control_frame_roundtrip_is_version_4():
    cf = ControlFrame("hb", {"snapshot": {"n": 3, "compute_s": 0.5,
                                          "nested": [1, (2, 3), None]}})
    blob = frame(cf)
    # control frames bumped the wire to v2; the reliability fields
    # (extent `attempt` + envelope `retryable`) bumped it to v3; the
    # decode-session fields (extent `kind`/`pos`/`session`) bumped it to
    # v4 — an older speaker must reject the frame loudly, not misparse
    assert blob[2] == FRAME_VERSION == 4
    back = unframe(blob)
    assert isinstance(back, ControlFrame)
    assert back.kind == "hb"
    assert back.payload["snapshot"]["n"] == 3
    assert back.payload["snapshot"]["nested"] == [1, (2, 3), None]


def test_control_frame_framed_stream_roundtrip():
    a, b = socket.socketpair()
    try:
        send_framed(a, ControlFrame("hello", {"token": "t0", "pid": 42}))
        got = recv_framed(b)
        assert got.kind == "hello" and got.payload["pid"] == 42
    finally:
        a.close()
        b.close()


# -- cross-process channels: expect/dial + the accept guard -------------------

def test_expect_dial_channel_roundtrip():
    tr = TcpTransport()
    inbox, cid = tr.expect_channel(4, role="send")
    host, port = tr.address
    peer = dial_channel(host, port, cid, role="recv", capacity=4)
    env = BatchEnvelope([RowExtent(1, 0, 0, 1)], b"xyz")
    inbox.send(env)
    got = peer.recv()
    assert got.blob == b"xyz" and got.extents == env.extents
    inbox.kill()
    peer.kill()
    tr.close()


def test_unexpect_channel_refuses_late_dial():
    tr = TcpTransport()
    ch, cid = tr.expect_channel(2, role="send")
    host, port = tr.address
    tr.unexpect_channel(cid)
    late = dial_channel(host, port, cid, role="recv", capacity=2)
    with pytest.raises(ChannelClosed):
        late.recv()
    ch.kill()
    late.kill()
    tr.close()


def test_accept_loop_survives_half_open_hello():
    """A client that connects and stalls mid-hello (2 of the 4 cid bytes)
    must not pin the accept thread: it is timed out and dropped, and the
    next well-behaved dial completes."""
    tr = TcpTransport()
    tr.handshake_timeout_s = 0.3        # instance override, test-fast
    ch, cid = tr.expect_channel(2, role="send")
    host, port = tr.address
    stalled = socket.create_connection((host, port))
    try:
        stalled.sendall(struct.pack("<I", cid)[:2])     # ...and stall
        t0 = time.monotonic()
        peer = dial_channel(host, port, cid, role="recv", capacity=2)
        ch.send(BatchEnvelope([RowExtent(1, 0, 0, 1)], b"ok"))
        assert peer.recv().blob == b"ok"
        # served the good client shortly after the guard fired, not never
        assert time.monotonic() - t0 < 10.0
    finally:
        stalled.close()
        ch.kill()
        peer.kill()
        tr.close()


# -- worker graph-factory resolution ------------------------------------------

def test_load_graph_factory_module_and_file_forms():
    by_file = load_graph_factory(GRAPHS + ":mlp_graph")
    assert len(by_file().nodes) == 6
    by_mod = load_graph_factory("tests._worker_graphs:mlp_graph")
    assert len(by_mod().nodes) == len(by_file().nodes)


def test_load_graph_factory_rejects_bad_specs():
    with pytest.raises(ValueError):
        load_graph_factory("no_colon_here")
    with pytest.raises(ValueError):
        load_graph_factory(":fn_only")
    with pytest.raises(ImportError):
        load_graph_factory("/nonexistent/path/graphs.py:fn")


# -- spawn failure cleanup ----------------------------------------------------

def test_spawn_timeout_cleans_up_no_orphans():
    """A worker binary that exits without ever dialing back must fail the
    spawn loudly and leave nothing behind (the conftest leak fixtures
    assert the 'nothing behind' half)."""
    g = mlp_graph()
    params = g.init(jax.random.PRNGKey(0))
    cfg = _cfg(python="/bin/false", spawn_timeout_s=1.0)
    with pytest.raises(ChannelClosed):
        supervised_engine(g, params, TopologySpec.chain(g, 2), cfg,
                          codecs=RAW)


# -- end-to-end over real processes -------------------------------------------

def test_procs_end_to_end_numerics_and_clean_drain():
    """Two worker processes serve a 2-stage chain: reference numerics,
    live telemetry flowing back over heartbeats, then a clean drain
    (workers say bye; nothing is killed)."""
    g = mlp_graph()
    params = g.init(jax.random.PRNGKey(0))
    eng, sup = supervised_engine(g, params, TopologySpec.chain(g, 2),
                                 _cfg(), codecs=RAW, max_batch=4)
    try:
        eng.start()
        xs = [np.random.default_rng(i).normal(size=(1, 16))
              .astype(np.float32) for i in range(12)]
        outs = [eng.submit(x) for x in xs]
        for x, f in zip(xs, outs):
            np.testing.assert_allclose(
                f.result(timeout=60),
                np.asarray(g.apply(params, x)), atol=1e-5)
        # telemetry: heartbeat-synthesized snapshots reach the report
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            snaps = [h.snapshot() for h in sup._handles]
            if sum(s["n"] for s in snaps) >= 2 * len(xs):
                break
            time.sleep(0.05)
        assert sum(h.snapshot()["n"] for h in sup._handles) == 2 * len(xs)
        rep = eng.report()
        assert rep.samples >= len(xs)
    finally:
        eng.shutdown()
        sup.close()
    assert not [e for e in sup.events if e["kind"] == "death"]


def test_worker_on_another_platform_is_refused():
    """A worker whose JAX came up on another platform than its
    supervisor's (on a chip host: the parent holds the chip, the child
    fell back to the CPU) is refused at start, with the reason, and
    stopped before it takes a request."""
    g = mlp_graph()
    params = g.init(jax.random.PRNGKey(0))
    eng, sup = supervised_engine(g, params, TopologySpec.chain(g, 1),
                                 _cfg(), codecs=RAW)
    sup.platform = "tpu"        # as if this process held the chip
    try:
        with pytest.raises(WorkerRefused,
                           match="'cpu'.*held by another process"):
            eng.start()
        (h,) = sup._handles
        assert h.platform == "cpu" and h.dead
        assert h.proc.poll() is not None
    finally:
        eng.shutdown()
        sup.close()


def test_procs_kill_heals_and_respawns_fast():
    """The CI smoke: 2 process replicas on stage 0, SIGKILL one, the
    stage heals (chain keeps answering) and the supervisor respawns it
    within the backoff window — seconds, not minutes."""
    from tools.chaos import Chaos
    g = mlp_graph()
    params = g.init(jax.random.PRNGKey(0))
    topo = TopologySpec.chain(g, 2).with_replicas(0, 2)
    eng, sup = supervised_engine(g, params, topo, _cfg(), codecs=RAW,
                                 max_batch=4)
    chaos = Chaos(sup)
    try:
        eng.start()
        x = np.random.default_rng(0).normal(size=(1, 16)).astype(np.float32)
        ref = np.asarray(g.apply(params, x))
        np.testing.assert_allclose(eng.submit(x).result(timeout=60), ref,
                                   atol=1e-5)
        chaos.kill(chaos.pick(stage=0))
        chaos.wait_death(stage=0, timeout=30)
        # the chain answers while degraded...
        np.testing.assert_allclose(eng.submit(x).result(timeout=60), ref,
                                   atol=1e-5)
        # ...and the stage is re-grown through scale() shortly after
        chaos.wait_respawn(stage=0, timeout=30)
        assert chaos.wait_stage_full(eng.dispatcher, 0, timeout=30) == 2
        np.testing.assert_allclose(eng.submit(x).result(timeout=60), ref,
                                   atol=1e-5)
    finally:
        eng.shutdown()
        sup.close()
