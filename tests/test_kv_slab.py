"""The per-replica KV slab: one device-resident buffer per decode layer
with a slot per resident session and a scratch row, read and written
inside the one jitted step, the slab donated.  Slot reuse after a close,
LRU hand-over, padded waves that touch only their own slots (with and
without the ``decode_attention`` kernel), donation, recovery from a
failed step, and the slot counters — every token bit-identical to the
single-device reference."""
import itertools
import threading

import jax
import numpy as np
import pytest

from repro.models.lm_graph import pipeline_decode_reference
from repro.runtime import InferenceEngine, TopologySpec
from repro.runtime.dispatcher import DispatcherCodecs
from repro.runtime.node import _Decoded
from repro.runtime.session import SessionStore
from repro.runtime.wire import K_OPEN, K_STEP, RowExtent, WireCodec
from tests._worker_graphs import lm_graph

CODECS = DispatcherCodecs(data=WireCodec("raw", "none"),
                          weights=WireCodec("raw", "none"))
PROMPTS = [[1, 5, 9, 2], [3, 3, 7], [2, 8, 4, 6, 1], [11, 0, 5, 5]]
_rid = itertools.count()


def build(stages: int = 2, capacity: int = 64, kernel: bool = False):
    """An engine over the tiny decoder; with ``kernel`` its attention reads
    the slab through the ``decode_attention`` kernel."""
    g = lm_graph(use_kernel=kernel)
    params = g.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(g, TopologySpec.chain(g, stages,
                                                session_capacity=capacity),
                          codecs=CODECS, max_batch=8)
    eng.configure(params)
    return g, params, eng


def ref(g, params, prompt, m):
    return pipeline_decode_reference(g, params, prompt, m)


def slots(eng, sid):
    """Each stage's slot for ``sid`` — read without ``get``, which would
    refresh the session's LRU position."""
    return [n.sessions._slots.get(sid) for n in eng.dispatcher.nodes]


def test_claim_gives_own_then_lowest_free_then_least_recent_slot():
    store = SessionStore(capacity=3)
    try:
        assert [store.claim(s) for s in "abc"] == [(0, False), (1, False),
                                                   (2, False)]
        assert store.claim("b") == (1, False)       # a re-open keeps its slot
        assert store.pop("a") == 0
        assert store.claim("d") == (0, False)       # the lowest free slot
        store.get("c")                              # b is now least recent
        assert store.claim("e") == (1, True)        # b's slot, b evicted
        assert store.get("b") is None and sorted(store.keys()) == list("cde")
    finally:
        store.clear()


def test_put_refuses_a_slot_another_session_holds():
    store = SessionStore(capacity=2)
    try:
        assert store.put("a", 0) is None
        assert store.put("a", 0) is None            # its own slot again
        for bad in (0, -1):
            with pytest.raises(ValueError):
                store.put("b", bad)
        assert store.keys() == ["a"]
    finally:
        store.clear()


# -- one node driven wave by wave (a 1-stage chain, threads not started) ------

def frame(kind: int, sid: str, toks, pos: int = 0) -> _Decoded:
    e = RowExtent(next(_rid), sid, 0, 1, kind=kind, pos=pos, session=sid)
    return _Decoded([e], {"": np.asarray([toks], np.int32)})


def wave(node, frames) -> dict:
    """One merged wave through the node; each session's next token."""
    outs, failures = node._decode_group(frames)
    assert not failures, failures[0].error
    return {ext[0].session: int(np.argmax(next(iter(res.values()))[0, -1]))
            for ext, res in outs}


def slab_rows(node) -> list[np.ndarray]:
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(node._slab)]


@pytest.mark.parametrize("kernel", [False, True])
def test_padded_wave_writes_only_its_slots_and_the_scratch_row(kernel):
    g, params, eng = build(stages=1, capacity=4, kernel=kernel)
    node = eng.dispatcher.nodes[0]
    try:
        first = {}
        for i, p in enumerate(PROMPTS):
            first.update(wave(node, [frame(K_OPEN, f"s{i}", p)]))
        assert [node.sessions.get(f"s{i}") for i in range(4)] == [0, 1, 2, 3]
        before = slab_rows(node)
        picked = [0, 2, 3]                       # 3 rows, padded to 4
        nxt = wave(node, [frame(K_STEP, f"s{i}", [first[f"s{i}"]],
                                len(PROMPTS[i])) for i in picked])
        changed = set()
        for old, new in zip(before, slab_rows(node)):
            assert old.shape[0] == 5             # capacity + scratch row
            changed |= {r for r in range(5)
                        if not np.array_equal(old[r], new[r])}
        assert changed == {0, 2, 3, 4}
        for i in picked:
            assert [first[f"s{i}"], nxt[f"s{i}"]] == ref(g, params,
                                                         PROMPTS[i], 2)
    finally:
        node._release_kv()
        eng.shutdown()


def test_step_and_slot_write_donate_the_slab():
    g, params, eng = build(stages=1, capacity=4)
    node = eng.dispatcher.nodes[0]
    try:
        tok = wave(node, [frame(K_OPEN, "a", PROMPTS[0])])["a"]
        old = jax.tree_util.tree_leaves(node._slab)
        toks = [tok, wave(node, [frame(K_STEP, "a", [tok],
                                       len(PROMPTS[0]))])["a"]]
        assert old and all(a.is_deleted() for a in old)
        old = jax.tree_util.tree_leaves(node._slab)
        wave(node, [frame(K_OPEN, "b", PROMPTS[1])])
        assert all(a.is_deleted() for a in old)
        assert not any(a.is_deleted()
                       for a in jax.tree_util.tree_leaves(node._slab))
        assert toks == ref(g, params, PROMPTS[0], 2)
    finally:
        node._release_kv()
        eng.shutdown()


def test_a_failed_step_drops_the_slab_and_every_session():
    """A step that raises after consuming the donated slab: the wave's
    sessions fail, every other session of the replica is SessionLost
    (never a step on deleted buffers), and a re-open serves again."""
    g, params, eng = build(stages=1, capacity=4)
    node = eng.dispatcher.nodes[0]
    real = node._decode_apply

    def faulty(*args):
        real(*args)
        raise RuntimeError("injected fault after the donated step")

    try:
        first = {}
        for sid, p in zip("ab", PROMPTS):
            first.update(wave(node, [frame(K_OPEN, sid, p)]))
        donated = jax.tree_util.tree_leaves(node._slab)
        node._decode_apply = faulty
        outs, failures = node._decode_group(
            [frame(K_STEP, "a", [first["a"]], len(PROMPTS[0]))])
        assert not outs and "injected fault" in failures[0].error
        assert all(a.is_deleted() for a in donated)
        assert node._slab is None and len(node.sessions) == 0
        node._decode_apply = real
        outs, failures = node._decode_group(
            [frame(K_STEP, "b", [first["b"]], len(PROMPTS[1]))])
        assert not outs and failures[0].error.startswith("SessionLost")
        hist = PROMPTS[1] + [first["b"]]
        again = wave(node, [frame(K_OPEN, "b", hist)])["b"]
        last = wave(node, [frame(K_STEP, "b", [again], len(hist))])["b"]
        assert [first["b"], again, last] == ref(g, params, PROMPTS[1], 3)
    finally:
        node._release_kv()
        eng.shutdown()


def test_a_failed_slot_write_drops_the_slab_and_every_session():
    g, params, eng = build(stages=1, capacity=4)
    node = eng.dispatcher.nodes[0]
    real = node._write_slot

    def faulty(*args):
        real(*args)
        raise RuntimeError("injected fault after the donated write")

    try:
        tok = wave(node, [frame(K_OPEN, "a", PROMPTS[0])])["a"]
        node._write_slot = faulty
        outs, failures = node._decode_group([frame(K_OPEN, "b", PROMPTS[1])])
        assert not outs and "injected fault" in failures[0].error
        assert node._slab is None and len(node.sessions) == 0
        node._write_slot = real
        hist = PROMPTS[0] + [tok]
        again = wave(node, [frame(K_OPEN, "a", hist)])["a"]
        assert [tok, again] == ref(g, params, PROMPTS[0], 2)
    finally:
        node._release_kv()
        eng.shutdown()


def test_precompile_covers_every_decode_wave_size():
    """After ``precompile()`` no slot write and no step of any wave size
    compiles (prefills compile per prompt length at first use, and the
    slab's allocation at the first open)."""
    g, params, eng = build(stages=1, capacity=8)
    node = eng.dispatcher.nodes[0]
    eng.precompile()
    compiled: list[str] = []

    def on(event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(kw.get("fun_name", ""))

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        first = {}
        for i, p in enumerate(PROMPTS):
            first.update(wave(node, [frame(K_OPEN, f"s{i}", p)]))
        for b in range(1, len(PROMPTS) + 1):     # 1, 2, 3 (padded), 4 rows
            wave(node, [frame(K_STEP, f"s{i}", [first[f"s{i}"]],
                              len(PROMPTS[i])) for i in range(b)])
        assert any("prefill_fn" in f for f in compiled)     # it listens
        assert not [f for f in compiled
                    if "step_fn" in f or "write_fn" in f], compiled
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
        node._release_kv()
        eng.shutdown()


# -- through the engine ------------------------------------------------------

def test_slot_freed_by_close_is_reused():
    g, params, eng = build()
    try:
        eng.start()
        keep = eng.generate(PROMPTS[0], 6, session_id="keep")
        got_keep = [next(keep)]
        got_a = list(eng.generate(PROMPTS[1], 4, session_id="a"))
        # the close freed "a"'s slot on every stage; "keep" still holds 0
        assert slots(eng, "a") == [None, None]
        assert slots(eng, "keep") == [0, 0]
        b = eng.generate(PROMPTS[2], 4, session_id="b")
        got_b = [next(b)]
        assert slots(eng, "b") == [1, 1]         # "a"'s slot, reused
        got_b += list(b)
        got_keep += list(keep)
        assert got_keep == ref(g, params, PROMPTS[0], 6)
        assert got_a == ref(g, params, PROMPTS[1], 4)
        assert got_b == ref(g, params, PROMPTS[2], 4)
        assert all(len(n.sessions) == 0 for n in eng.dispatcher.nodes)
    finally:
        eng.shutdown()


def test_lru_eviction_hands_the_slot_over_and_the_evicted_reprefills():
    g, params, eng = build(capacity=2)
    m = 5
    try:
        eng.start()
        gens = {s: eng.generate(p, m, session_id=s, restart="always")
                for s, p in zip(("s1", "s2", "s3"), PROMPTS)}
        out = {s: [] for s in gens}
        out["s1"].append(next(gens["s1"]))
        out["s2"].append(next(gens["s2"]))
        out["s1"].append(next(gens["s1"]))      # s2 is now least recent
        s2_slots = slots(eng, "s2")
        out["s3"].append(next(gens["s3"]))      # evicts s2, takes its slot
        assert slots(eng, "s2") == [None, None]
        assert slots(eng, "s3") == s2_slots
        for s in ("s1", "s3"):                  # the survivors, unharmed
            out[s] += list(gens[s])
        out["s2"] += list(gens["s2"])           # SessionLost -> re-prefill
        for s, p in zip(("s1", "s2", "s3"), PROMPTS):
            assert out[s] == ref(g, params, p, m), s
        totals = [pn["totals"] for pn in eng.report().per_node]
        assert [t["kv_evictions"] for t in totals] == [1, 1]
    finally:
        eng.shutdown()


def test_a_step_that_raises_leaves_the_replica_serving_by_reprefill():
    """The fault strikes after the step consumed the donated slab: the
    replica must drop every session, never step on the deleted buffers,
    and serve the re-prefilled sessions to the reference's tokens."""
    g, params, eng = build()
    node = eng.dispatcher.nodes[1]
    real, calls = node._decode_apply, []

    def faulty(*args):
        out = real(*args)
        calls.append(len(calls))
        if len(calls) == 3:
            raise RuntimeError("injected fault after the donated step")
        return out

    node._decode_apply = faulty
    m = 8
    outs: list[list[int]] = [[] for _ in PROMPTS[:2]]
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
              for i, p in enumerate(PROMPTS[:2])]
        for t in ts:
            t.start()
        for t in ts:
            t.join(300)
        assert not any(t.is_alive() for t in ts), "generation hung"
        assert not errs, errs
        assert outs == [ref(g, params, p, m) for p in PROMPTS[:2]]
        assert len(calls) > 3                   # it stepped on afterwards
        # every session the fault dropped opened again on that replica
        t = eng.report().per_node[1]["totals"]
        assert t["prefills"] > len(PROMPTS[:2])
    finally:
        eng.shutdown()


def test_slot_counters_match_the_traffic():
    """Capacity 1: "a" opens and steps twice alone (1 live slot per
    wave), then "b" opens, evicting "a", and steps twice."""
    g, params, eng = build(capacity=1)
    try:
        eng.start()
        eng.reset_window()
        a = eng.generate(PROMPTS[0], 8, session_id="a", restart="never")
        got_a = [next(a) for _ in range(3)]
        got_b = list(eng.generate(PROMPTS[1], 3, session_id="b"))
        a.close()
        assert got_a == ref(g, params, PROMPTS[0], 3)
        assert got_b == ref(g, params, PROMPTS[1], 3)
        rep = eng.report()
        snaps = [n.snapshot() for n in eng.dispatcher.nodes]
        for pn, snap in zip(rep.per_node, snaps):
            for t in (pn["totals"], snap):
                assert t["apply_n"] == t["step_rows"] == 4
                assert t["kv_slots_sum"] == 4
                assert t["kv_evictions"] == 1
    finally:
        eng.shutdown()
