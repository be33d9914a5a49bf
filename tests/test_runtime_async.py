"""Async serving runtime: concurrent multi-client submit, FIFO-per-client
ordering, admission backpressure, continuous batching, clean shutdown."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import LayerGraph
from repro.runtime import AdmissionFull, InferenceEngine, TopologySpec
from repro.runtime.dispatcher import DispatcherCodecs
from repro.runtime.wire import WireCodec

D = 16


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


RAW = DispatcherCodecs(data=WireCodec("raw", "none"),
                       weights=WireCodec("raw", "none"))


def make_engine(num_nodes=4, transport="inproc", **kw):
    g = mlp_graph()
    params = g.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(
        g, TopologySpec.chain(g, num_nodes, transport=transport), RAW, **kw)
    eng.configure(params)
    return g, params, eng


def sample(i: int) -> np.ndarray:
    rng = np.random.default_rng(i)
    return rng.normal(size=(1, D)).astype(np.float32)


def test_concurrent_submit_from_many_threads():
    """N client threads stream disjoint inputs concurrently; every client
    sees its own results, in its own submission order, numerically equal
    to the single-device reference."""
    g, params, eng = make_engine(num_nodes=4, max_batch=4)
    n_clients, per_client = 6, 5
    refs = {c: [np.asarray(g.apply(params, jnp.asarray(sample(100 * c + i))))
                for i in range(per_client)] for c in range(n_clients)}
    results: dict[int, list] = {}
    errors: list = []

    def client(c):
        try:
            xs = [sample(100 * c + i) for i in range(per_client)]
            results[c] = list(eng.submit_stream(xs, client_id=c))
        except Exception as e:                      # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    eng.shutdown()
    assert not errors
    for c in range(n_clients):
        assert len(results[c]) == per_client
        for got, ref in zip(results[c], refs[c]):
            np.testing.assert_allclose(got, ref, atol=1e-5)


def test_fifo_per_client_ordering_under_interleaving():
    """Interleaved submits from two clients: each client's futures resolve
    to exactly its own inputs' outputs, in submission order."""
    g, params, eng = make_engine(num_nodes=3, max_batch=8)
    futs = {0: [], 1: []}
    inputs = {0: [], 1: []}
    for i in range(10):
        c = i % 2
        x = sample(i)
        inputs[c].append(x)
        futs[c].append(eng.submit(x, client_id=c))
    for c in (0, 1):
        for fut, x in zip(futs[c], inputs[c]):
            ref = np.asarray(g.apply(params, jnp.asarray(x)))
            np.testing.assert_allclose(fut.result(timeout=30), ref,
                                       atol=1e-5)
    eng.shutdown()


def test_backpressure_bounded_admission():
    """With the head of the chain stalled, the bounded admission queue
    fills and submit() raises (non-blocking) or times out (blocking)."""
    g, params, eng = make_engine(num_nodes=2, max_batch=1,
                                 admission_depth=2, queue_depth=1)
    gate = threading.Event()
    node0 = eng.dispatcher.nodes[0]
    orig_apply = node0._apply

    def stalled(boundary):
        gate.wait(timeout=60)
        return orig_apply(boundary)

    node0._apply = stalled
    # saturate: with the head stalled the system reaches a fixed point of
    # admitted requests (processing + inbox + pump hand + admission queue);
    # past that every put fails
    admitted = []
    fails = 0
    for i in range(32):                     # far more than total capacity
        try:
            admitted.append((i, eng.submit(sample(i), block=False)))
        except AdmissionFull:
            fails += 1
            time.sleep(0.02)
    assert fails > 0
    assert 2 <= len(admitted) < 32
    with pytest.raises(AdmissionFull):      # blocking submit times out too
        eng.submit(sample(99), block=True, timeout=0.2)
    gate.set()                              # unblock and let them finish
    for i, fut in admitted:
        ref = np.asarray(g.apply(params, jnp.asarray(sample(i))))
        np.testing.assert_allclose(fut.result(timeout=60), ref, atol=1e-5)
    eng.shutdown()


def test_clean_shutdown_with_inflight_requests():
    """shutdown(drain=True) completes every admitted request before
    stopping the chain; later submits are refused."""
    g, params, eng = make_engine(num_nodes=3, max_batch=2)
    futs = [eng.submit(sample(i)) for i in range(12)]
    eng.shutdown(drain=True)
    for i, fut in enumerate(futs):
        assert fut.done()
        ref = np.asarray(g.apply(params, jnp.asarray(sample(i))))
        np.testing.assert_allclose(fut.result(), ref, atol=1e-5)
    for node in eng.dispatcher.nodes:
        assert not any(t.is_alive() for t in node._threads)
    with pytest.raises(RuntimeError):
        eng.submit(sample(0))


@pytest.mark.parametrize("transport", ["inproc", "tcp"])
def test_continuous_batching_actually_batches(transport):
    """Stall the head node's compute stage, pile requests up, release: the
    next merge must compute >1 request in one apply (fewer waves than
    requests), and
    the staged egress must hand the merged batch on in fewer envelopes
    than it has requests (batch-level hand-off): codec passes over tcp,
    resident envelopes in process."""
    g, params, eng = make_engine(num_nodes=2, transport=transport,
                                 max_batch=8, admission_depth=64,
                                 queue_depth=8)
    gate = threading.Event()
    node0 = eng.dispatcher.nodes[0]
    orig_apply = node0._apply
    node0._apply = lambda b: (gate.wait(timeout=60), orig_apply(b))[1]
    futs = [eng.submit(sample(i)) for i in range(6)]
    # all six are admitted (submit returns post-admission); give the
    # ingress stage a moment to decode them into the compute queue
    deadline = time.perf_counter() + 10
    while node0._to_compute.qsize() < 2 and time.perf_counter() < deadline:
        time.sleep(0.01)
    time.sleep(0.1)
    gate.set()
    outs = [f.result(timeout=60) for f in futs]
    eng.shutdown()
    snap = node0.snapshot()
    assert snap["n"] == 6
    assert snap["waves"] < snap["n"]          # some wave merged >1 request
    on, off = (("resident", "encodes") if transport == "inproc"
               else ("encodes", "resident"))
    assert snap[on] == snap["waves"]          # one hand-off per bucket,
    assert snap[off] == 0                     # not per request
    for i, out in enumerate(outs):
        ref = np.asarray(g.apply(params, jnp.asarray(sample(i))))
        np.testing.assert_allclose(out, ref, atol=1e-5)


def test_report_serving_metrics():
    """EngineReport exposes per-node per-stage utilization, queue depth,
    batch occupancy, and latency percentiles over the measurement window.
    Stage utilizations are fractions of the reset->report wall clock, so
    each stays in [0, 1] even though the three stages overlap."""
    g, params, eng = make_engine(num_nodes=4, max_batch=4)
    xs = [sample(i) for i in range(8)]
    outs, rep = eng.run(xs)
    eng.shutdown()
    assert rep.samples == 8 and len(outs) == 8
    assert rep.p50_latency_s > 0 and rep.p99_latency_s >= rep.p50_latency_s
    for pn in rep.per_node:
        for key in ("util_decode_raw", "util_compute_raw",
                    "util_encode_raw"):
            assert 0.0 <= pn[key] <= 1.0
        assert pn["queue_depth_max"] >= 1
        assert pn["batch_mean"] >= 1.0
    assert any(pn["util_compute_raw"] > 0 for pn in rep.per_node)


def test_stage_overlap_observable():
    """The 3-stage split books codec time on the ingress/egress threads:
    after a real run every node shows nonzero decode and encode busy time
    recorded separately from compute (the overlap the staging buys)."""
    g = mlp_graph()
    params = g.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(
        g, 3, DispatcherCodecs(data=WireCodec("zfp", "none", zfp_rate=16),
                               weights=WireCodec("raw", "none")),
        max_batch=4)
    eng.configure(params)
    outs, rep = eng.run([sample(i) for i in range(12)])
    eng.shutdown()
    for node in eng.dispatcher.nodes:
        snap = node.snapshot()
        assert snap["busy_decode_s"] > 0
        assert snap["busy_compute_s"] > 0
        assert snap["busy_encode_s"] > 0
    assert len(outs) == 12


def test_error_propagation_fails_future_keeps_node_alive():
    """An exception inside a node's apply fails exactly the affected
    requests' futures (with the remote traceback) and the chain keeps
    serving subsequent batches."""
    from repro.runtime import NodeError
    g, params, eng = make_engine(num_nodes=3, max_batch=2)
    node1 = eng.dispatcher.nodes[1]
    orig_apply = node1._apply
    state = {"boom": True}

    def flaky(boundary):
        if state["boom"]:
            state["boom"] = False
            raise ValueError("injected-apply-failure")
        return orig_apply(boundary)

    node1._apply = flaky
    bad = eng.submit(sample(0))
    with pytest.raises(NodeError) as ei:
        bad.result(timeout=60)
    assert "injected-apply-failure" in str(ei.value)   # remote traceback
    # the node survived: a later request completes correctly
    good = eng.submit(sample(1)).result(timeout=60)
    ref = np.asarray(g.apply(params, jnp.asarray(sample(1))))
    np.testing.assert_allclose(good, ref, atol=1e-5)
    for node in eng.dispatcher.nodes:
        assert all(t.is_alive() for t in node._threads)
    eng.shutdown()


def test_error_propagation_codec_failure():
    """A decode failure mid-chain also fails the future instead of
    stranding it (corrupt blob injected at the head node's outbox).  The
    hop frames (tcp), so the next node's codec runs on it."""
    from repro.runtime import NodeError
    g, params, eng = make_engine(num_nodes=2, transport="tcp", max_batch=1)
    node1 = eng.dispatcher.nodes[1]
    state = {"boom": True}

    class Corrupting:
        def __init__(self, inner):
            self._inner = inner

        def decode_tree(self, blob):
            if state["boom"]:
                state["boom"] = False
                raise ValueError("injected-decode-failure")
            return self._inner.decode_tree(blob)

        def __getattr__(self, name):
            return getattr(self._inner, name)

    node1.data_codec = Corrupting(node1.data_codec)
    bad = eng.submit(sample(0))
    with pytest.raises(NodeError):
        bad.result(timeout=60)
    good = eng.submit(sample(1)).result(timeout=60)
    ref = np.asarray(g.apply(params, jnp.asarray(sample(1))))
    np.testing.assert_allclose(good, ref, atol=1e-5)
    eng.shutdown()


def test_error_isolated_to_failing_bucket():
    """When a merged group spans two shape buckets and only one bucket's
    apply raises, the sibling bucket's requests still succeed."""
    from repro.runtime import NodeError
    g, params, eng = make_engine(num_nodes=2, max_batch=8)
    node0 = eng.dispatcher.nodes[0]
    gate = threading.Event()
    orig_apply = node0._apply

    def selective(boundary):
        gate.wait(timeout=60)
        if next(iter(boundary.values())).ndim == 3:   # the (1, 8, D) bucket
            raise ValueError("bucket-poison")
        return orig_apply(boundary)

    node0._apply = selective
    x_ok = sample(0)                                  # (1, D)
    x_bad = np.stack([sample(1)] * 8, axis=1)         # (1, 8, D): own bucket
    f_ok = eng.submit(x_ok)
    f_bad = eng.submit(x_bad)
    deadline = time.perf_counter() + 10
    while (node0._to_compute.qsize() + node0.inbox.qsize()) < 1 \
            and time.perf_counter() < deadline:
        time.sleep(0.01)
    time.sleep(0.1)
    gate.set()
    with pytest.raises(NodeError, match="bucket-poison"):
        f_bad.result(timeout=60)
    ref = np.asarray(g.apply(params, jnp.asarray(x_ok)))
    np.testing.assert_allclose(f_ok.result(timeout=60), ref, atol=1e-5)
    eng.shutdown()


def test_unstaged_mode_parity():
    """The kept PR 1 single-thread path (staged=False, per-request wire)
    still produces correct results — it is the serve_load A/B baseline."""
    g = mlp_graph()
    params = g.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(g, 3, RAW, max_batch=4, staged=False)
    eng.configure(params)
    outs, rep = eng.run([sample(i) for i in range(8)])
    eng.shutdown()
    for i, out in enumerate(outs):
        ref = np.asarray(g.apply(params, jnp.asarray(sample(i))))
        np.testing.assert_allclose(out, ref, atol=1e-5)
    # per-request wire: one encode per request, not per bucket; the
    # legacy path never hands on resident, so every hop encodes
    snaps = [n.snapshot() for n in eng.dispatcher.nodes]
    assert all(s["encodes"] == s["n"] == 8 for s in snaps)
    assert all(s["resident"] == 0 for s in snaps)


# -- per-request deadlines (the reliability layer's reaper) -------------------

def slow_mlp_graph(delay_s: float = 0.4, d: int = D) -> LayerGraph:
    """One-layer MLP whose compute dwells ``delay_s`` on the host (via a
    callback, so the dwell survives jit) — deterministic loser of any
    race against a sub-dwell deadline."""
    g = LayerGraph("slow-mlp", jax.ShapeDtypeStruct((1, d), np.float32))

    def nap(xh):
        time.sleep(delay_s)
        return np.asarray(xh)

    def fn(p, x):
        x = jax.pure_callback(nap, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return jnp.tanh(x @ p["w"])

    g.layer("fc0", fn, {"w": jax.ShapeDtypeStruct((d, d), np.float32)},
            ("",), jax.ShapeDtypeStruct((1, d), np.float32),
            flops=2.0 * d * d)
    return g


def test_deadline_expires_before_slow_result_late_result_dropped():
    """A 0.05s deadline against a 0.4s compute: the future fails with
    DeadlineExceeded well before the result exists, the late result is
    dropped by the at-most-once merge (never delivered), retention is
    cleaned up, and the chain keeps serving."""
    from repro.runtime.dispatcher import DeadlineExceeded
    g = slow_mlp_graph()
    params = g.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(g, 1, RAW, max_batch=1)
    eng.configure(params)
    eng.start()
    # warm: compile outside the timed window
    eng.submit(sample(0)).result(timeout=60)

    t0 = time.monotonic()
    fut = eng.submit(sample(1), deadline_s=0.05)
    with pytest.raises(DeadlineExceeded, match="0.05"):
        fut.result(timeout=30)
    took = time.monotonic() - t0
    assert took < 5.0, f"deadline fired after {took:.2f}s, not ~0.05s"
    assert eng.dispatcher.replay_stats.deadlines_expired == 1
    # the late result resolves to a no-op; the NEXT submit still works
    # and retention holds no ghost of the expired request
    ref = np.asarray(g.apply(params, jnp.asarray(sample(2))))
    np.testing.assert_allclose(eng.submit(sample(2)).result(timeout=60),
                               ref, atol=1e-5)
    assert not eng.dispatcher._retained
    eng.shutdown()


def test_deadline_met_resolves_normally_and_cleans_retention():
    """A generous deadline never fires: the result arrives, the timer
    event resolves to a no-op, and the retained entry is dropped on
    delivery, not on expiry."""
    g = mlp_graph()
    params = g.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(g, 2, RAW, max_batch=2)
    eng.configure(params)
    eng.start()
    ref = np.asarray(g.apply(params, jnp.asarray(sample(3))))
    out = eng.submit(sample(3), deadline_s=60.0).result(timeout=60)
    np.testing.assert_allclose(out, ref, atol=1e-5)
    assert eng.dispatcher.replay_stats.deadlines_expired == 0
    assert not eng.dispatcher._retained
    eng.shutdown()
