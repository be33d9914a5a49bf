"""Autoregressive decode serving: v4 session extents on the wire, the
small-payload bypass, end-to-end multi-session generation (inproc AND
tcp) bit-identical to the single-device reference, per-step cross-hop
payloads O(d_model) instead of O(sequence), session survival across
scale()/reconfigure() fences, SessionLost semantics when recovery is
forbidden, LRU-eviction recovery, and the stream()/submit_stream()
deprecation shim.  The SIGKILL-mid-generation drill lives in
test_chaos.py."""
import threading
import time

import jax
import numpy as np
import pytest

from repro.runtime import InferenceEngine, TopologySpec
from repro.runtime.dispatcher import (DispatcherCodecs, NodeError,
                                      RetryPolicy)
from repro.runtime.session import (SessionLost, SessionStore,
                                   live_session_stores)
from repro.runtime.wire import (_RAW_BYPASS_MAGIC, BatchEnvelope, RowExtent,
                                WireCodec, frame, unframe)
from repro.models.lm_graph import decode_lm_graph, pipeline_decode_reference
from tests._worker_graphs import lm_graph, mlp_graph

# lossless data path so greedy decode is bit-identical across hops; the
# bypass threshold exercises the small-frame fast path on every step
DATA = WireCodec("raw", "lz4", small_bypass=4096)
CODECS = DispatcherCodecs(data=DATA, weights=WireCodec("raw", "none"))

PROMPTS = [[1, 5, 9, 2], [3, 3, 7], [2, 8, 4, 6, 1], [11, 0, 5, 5]]


def build(topology=None, graph=None, **kw):
    g = graph if graph is not None else lm_graph()
    params = g.init(jax.random.PRNGKey(0))
    topo = topology if topology is not None else TopologySpec.chain(g, 2)
    kw.setdefault("codecs", CODECS)
    kw.setdefault("max_batch", 4)
    eng = InferenceEngine(g, topo, **kw)
    eng.configure(params)
    return g, params, eng


def refs(g, params, prompts, m):
    return [pipeline_decode_reference(g, params, p, m) for p in prompts]


def run_sessions(eng, prompts, m, **gen_kw):
    """Drive one generate() per prompt on its own thread (concurrent
    sessions at DIFFERENT sequence positions); return the token lists,
    re-raising the first worker failure."""
    outs: list[list[int]] = [[] for _ in prompts]
    errs: list[BaseException] = []

    def one(i, p):
        try:
            for tok in eng.generate(p, m, **gen_kw):
                outs[i].append(tok)
        except BaseException as e:      # noqa: BLE001 - re-raised below
            errs.append(e)

    ts = [threading.Thread(target=one, args=(i, p))
          for i, p in enumerate(prompts)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(300)
    assert not any(t.is_alive() for t in ts), "generation hung"
    if errs:
        raise errs[0]
    return outs


# -- wire: v4 session extents -------------------------------------------------

def test_session_extents_roundtrip_on_the_wire():
    exts = [RowExtent(7, "c1", 0, 1, kind=1, pos=0, session="sess-a"),
            RowExtent(8, "c2", 1, 1, kind=2, pos=13, session="sess-b"),
            RowExtent(9, "c3", 2, 1)]  # plain rows carry the defaults
    blob = frame(BatchEnvelope(exts, b"payload"))
    back = unframe(blob)
    assert [(e.kind, e.pos, e.session) for e in back.extents] == \
        [(1, 0, "sess-a"), (2, 13, "sess-b"), (0, 0, None)]
    assert back.extents[0].client_id == "c1"
    assert back.blob == b"payload"


# -- wire: small-payload bypass -----------------------------------------------

def test_small_bypass_is_lossless_through_a_lossy_codec():
    lossy = WireCodec("q8", "lz4", small_bypass=4096)
    small = np.random.default_rng(0).normal(size=(1, 1, 16)) \
        .astype(np.float32)
    blob = lossy.encode_array(small)
    assert blob.startswith(_RAW_BYPASS_MAGIC)
    np.testing.assert_array_equal(lossy.decode_array(blob), small)
    # above the threshold the configured (lossy) codec path still runs
    big = np.random.default_rng(1).normal(size=(1, 300, 16)) \
        .astype(np.float32)
    blob = lossy.encode_array(big)
    assert not blob.startswith(_RAW_BYPASS_MAGIC)
    back = lossy.decode_array(blob)
    assert not np.array_equal(back, big)        # quantized, not copied
    np.testing.assert_allclose(back, big, atol=1e-1)


def test_small_bypass_zero_disables():
    codec = WireCodec("q8", "none", small_bypass=0)
    arr = np.ones((1, 4), np.float32)
    assert not codec.encode_array(arr).startswith(_RAW_BYPASS_MAGIC)


# -- end-to-end decode: inproc + tcp ------------------------------------------

def test_decode_inproc_multi_session_bit_identical():
    g, params, eng = build()
    try:
        eng.start()
        m = 8
        outs = run_sessions(eng, PROMPTS[:3], m)
        assert outs == refs(g, params, PROMPTS[:3], m)
    finally:
        eng.shutdown()


def test_decode_tcp_replicated_multi_session_bit_identical():
    g0 = lm_graph()
    topo = TopologySpec.chain(g0, 2, transport="tcp").with_replicas(0, 2)
    g, params, eng = build(topology=topo, graph=g0)
    try:
        eng.start()
        m = 8
        outs = run_sessions(eng, PROMPTS, m)
        assert outs == refs(g, params, PROMPTS, m)
    finally:
        eng.shutdown()


@pytest.mark.parametrize("transport", ["tcp", "link"])
def test_step_payload_is_10x_smaller_than_full_sequence_resend(transport):
    """THE decode contract: after prefill, each hop ships one token's
    activations — O(d_model) — not the growing sequence.  Measured on the
    stage-0 replica's outbound wire bytes, where they really cross a
    framing channel, against what resending the full sequence through the
    same codec would cost per step."""
    g0 = lm_graph()
    g, params, eng = build(
        topology=TopologySpec.chain(g0, 2, transport=transport), graph=g0)
    prompt, m = [1, 2, 3, 4, 5, 6, 7, 8], 30
    try:
        eng.start()
        gen = eng.generate(prompt, m)
        next(gen)                      # prefill + first token
        node = eng.dispatcher.stages[0].live_replicas()[0]
        node.reset_stats()
        for _ in range(m - 1):
            next(gen)
        snap = node.snapshot()
        assert snap["resident"] == 0 and snap["encodes"] == m - 1
        per_step = snap["payload_bytes"] / (m - 1)
        gen.close()
        # the full-sequence alternative: the final-prefix boundary
        # activations through the SAME codec
        d_model = 16
        full = np.zeros((1, len(prompt) + m, d_model), np.float32)
        full_bytes = len(DATA.encode_array(full))
        assert full_bytes / per_step >= 10.0, \
            f"per-step hop payload {per_step:.0f}B vs full-sequence " \
            f"resend {full_bytes}B: less than 10x saving"
    finally:
        eng.shutdown()


# -- elasticity: sessions survive scale() and reconfigure() -------------------

def _wait_tokens(outs, k, timeout=120.0):
    deadline = time.monotonic() + timeout
    while not all(len(o) >= k for o in outs):
        assert time.monotonic() < deadline, \
            f"sessions never reached {k} tokens: {[len(o) for o in outs]}"
        time.sleep(0.01)


def test_scale_during_generation_drops_zero_sessions():
    g0 = lm_graph()
    topo = TopologySpec.chain(g0, 2).with_replicas(0, 2)
    g, params, eng = build(
        topology=topo, graph=g0,
        retry_policy=RetryPolicy(max_attempts=4, backoff_s=0.05,
                                 retry_budget=64.0, refill_per_s=32.0))
    m = 12
    outs: list[list[int]] = [[] for _ in PROMPTS]
    errs: list[BaseException] = []

    def one(i, p):
        try:
            for tok in eng.generate(p, m):      # restart='auto' + policy
                outs[i].append(tok)
        except BaseException as e:      # noqa: BLE001 - asserted below
            errs.append(e)

    try:
        eng.start()
        ts = [threading.Thread(target=one, args=(i, p))
              for i, p in enumerate(PROMPTS)]
        for t in ts:
            t.start()
        _wait_tokens(outs, 2)
        # drain one replica (displaces its pinned sessions), then regrow
        eng.scale(0, 1)
        eng.scale(0, 2)
        for t in ts:
            t.join(300)
        assert not any(t.is_alive() for t in ts), "generation hung"
        assert not errs, f"sessions dropped across scale(): {errs}"
        assert outs == refs(g, params, PROMPTS, m)
    finally:
        eng.shutdown()


def test_reconfigure_during_generation_migrates_sessions():
    """A repartition invalidates EVERY stage's resident KV (layer ranges
    moved); active sessions must re-prefill onto the new partitioning and
    finish bit-identical — restart='always' needs no retry policy."""
    g, params, eng = build()
    m = 12
    outs: list[list[int]] = [[] for _ in PROMPTS[:3]]
    errs: list[BaseException] = []

    def one(i, p):
        try:
            for tok in eng.generate(p, m, restart="always"):
                outs[i].append(tok)
        except BaseException as e:      # noqa: BLE001 - asserted below
            errs.append(e)

    try:
        eng.start()
        ts = [threading.Thread(target=one, args=(i, p))
              for i, p in enumerate(PROMPTS[:3])]
        for t in ts:
            t.start()
        _wait_tokens(outs, 2)
        eng.dispatcher.reconfigure([2])     # 6 layers: [0,3,6] -> [0,2,6]
        for t in ts:
            t.join(300)
        assert not any(t.is_alive() for t in ts), "generation hung"
        assert not errs, f"sessions dropped across reconfigure(): {errs}"
        assert outs == refs(g, params, PROMPTS[:3], m)
    finally:
        eng.shutdown()


# -- loss of residency: SessionLost vs re-prefill -----------------------------

def test_eviction_with_restart_never_raises_sessionlost():
    """KV capacity 1: opening a second session evicts the first.  With
    restart='never' the evicted session raises SessionLost
    (retryable=False) — and the chain keeps serving other sessions AND
    plain single-shot traffic."""
    g0 = lm_graph()
    topo = TopologySpec.chain(g0, 2, session_capacity=1)
    g, params, eng = build(topology=topo, graph=g0)
    try:
        eng.start()
        s1 = eng.generate(PROMPTS[0], 4, restart="never")
        t1 = [next(s1)]                         # s1 resident
        s2 = eng.generate(PROMPTS[1], 4, restart="never")
        t2 = [next(s2)]                         # evicts s1 (capacity 1)
        assert SessionLost.retryable is False
        with pytest.raises(SessionLost):
            next(s1)
        # the survivor and one-shot traffic are unharmed
        t2.append(next(s2))
        s2.close()
        assert t2 == pipeline_decode_reference(g, params, PROMPTS[1], 4)[:2]
        x = np.asarray([PROMPTS[2]], np.int32)
        np.testing.assert_allclose(
            eng.submit(x).result(timeout=60),
            np.asarray(g.apply(params, x)), atol=1e-4)
    finally:
        eng.shutdown()


def test_eviction_thrash_recovered_by_reprefill():
    """Same capacity-1 store, restart='always': two interleaved sessions
    evict each other every step, and every step recovers by re-prefilling
    the retained history — slow, but bit-identical."""
    g0 = lm_graph()
    topo = TopologySpec.chain(g0, 2, session_capacity=1)
    g, params, eng = build(topology=topo, graph=g0)
    m = 5
    try:
        eng.start()
        gens = [eng.generate(p, m, restart="always") for p in PROMPTS[:2]]
        outs = [[], []]
        for _ in range(m):
            for o, gen in zip(outs, gens):
                o.append(next(gen))
        for gen in gens:
            gen.close()
        assert outs == refs(g, params, PROMPTS[:2], m)
    finally:
        eng.shutdown()


def test_legacy_unstaged_runtime_refuses_sessions():
    g, params, eng = build(staged=False)
    try:
        eng.start()
        with pytest.raises(SessionLost) as ei:
            next(eng.generate(PROMPTS[0], 2, restart="never"))
        assert "staged" in str(ei.value.__cause__)
    finally:
        eng.shutdown()


# -- generate() argument validation -------------------------------------------

def test_generate_validates_arguments():
    g, params, eng = build()
    try:
        eng.start()
        with pytest.raises(ValueError, match="non-empty prompt"):
            next(eng.generate([], 4))
        with pytest.raises(ValueError, match="max_new_tokens"):
            next(eng.generate([1, 2], 0))
        with pytest.raises(ValueError, match="KV capacity"):
            next(eng.generate([1, 2], 10_000))      # cache_len is 48
        with pytest.raises(ValueError, match="restart"):
            next(eng.generate([1, 2], 4, restart="sometimes"))
    finally:
        eng.shutdown()


def test_generate_requires_decode_capable_graph():
    g, params, eng = build(graph=mlp_graph())
    try:
        with pytest.raises(ValueError, match="not decode-capable"):
            next(eng.generate([1, 2], 4))
    finally:
        eng.shutdown(drain=False)


# -- stream() deprecation shim ------------------------------------------------

def test_stream_is_a_deprecated_alias_for_submit_stream():
    g, params, eng = build()
    xs = [np.asarray([p], np.int32) for p in PROMPTS[:2]]
    try:
        eng.start()
        want = [np.asarray(g.apply(params, x)) for x in xs]
        with pytest.warns(DeprecationWarning, match="submit_stream"):
            got = list(eng.stream(xs))
        for w, o in zip(want, got):
            np.testing.assert_allclose(o, w, atol=1e-4)
    finally:
        eng.shutdown()


# -- SessionStore unit semantics ----------------------------------------------

def test_session_store_lru_eviction_and_registry():
    store = SessionStore(capacity=2)
    assert store in live_session_stores()
    store.put("a", 1)
    store.put("b", 2)
    assert store.get("a") == 1          # refreshes a's LRU slot
    store.put("c", 3)                   # evicts b, the least recent
    assert store.get("b") is None
    assert sorted(store.keys()) == ["a", "c"]
    assert store.pop("a") == 1 and store.pop("a") is None
    store.put("d", 4)
    store.clear()                       # the conftest residue guard's path
    assert len(store) == 0
