"""The serving chain's running totals (repro.runtime.spans): spans, hand-off
waits and counters per replica, the dispatcher's and the decode loop's,
as the engine reports them and a supervised worker ships them."""
import jax
import numpy as np
import pytest

from repro.runtime import InferenceEngine, TopologySpec
from repro.runtime.dispatcher import DispatcherCodecs
from repro.runtime.node import stage_stats
from repro.runtime.spans import Spans
from repro.runtime.supervisor import WorkerHandle
from repro.runtime.transport import ChannelClosed
from repro.runtime.wire import WireCodec
from tests._worker_graphs import D, lm_graph, mlp_graph

RAW = DispatcherCodecs(data=WireCodec("raw", "none"),
                       weights=WireCodec("raw", "none"))


def _pow2(n: int) -> int:
    return 1 << (n - 1).bit_length()


def test_spans_keep_totals_and_build_names_once():
    s = Spans("stage3", ("apply", "d2h"), ("inbox",), ("rows",))
    assert s._spans["apply"][0] == "defer.stage3.apply"
    assert set(s.totals) == {"apply_s", "apply_n", "d2h_s", "d2h_n",
                             "wait_inbox_s", "wait_inbox_n", "rows"}
    with s.span("apply", rid=7, rows=2):
        pass
    with s.span("apply", rid=8, rows=1):
        pass
    assert s.totals["apply_n"] == 2 and s.totals["apply_s"] > 0
    s.waited("inbox", 10.0, 3, now=10.5)
    s.waited("inbox", None, 5)          # crossed a process: missing, not 0
    s.add(rows=4)
    assert (s.totals["wait_inbox_s"], s.totals["wait_inbox_n"]) == (0.5, 3)
    assert s.totals["rows"] == 4
    s.reset()
    assert s.snapshot() == s.zero


@pytest.mark.parametrize("transport", ["inproc", "tcp"])
def test_oneshot_chain_totals_match_the_traffic(transport):
    """Per replica of a 2-stage chain: compute_s is prefill + apply + d2h,
    and requests, rows and the bytes copied each way follow from the
    shapes sent (each request alone in its wave, padded to a power of
    two rows).  In process the inner hop stays on the device: stage 0
    copies nothing out and stage 1 nothing in; over tcp both do."""
    g = mlp_graph(4)
    eng = InferenceEngine(g, TopologySpec.chain(g, 2, transport=transport),
                          RAW, max_batch=4)
    eng.configure(g.init(jax.random.PRNGKey(0)))
    rows = [1, 3, 2, 1]
    eng.start()
    eng.reset_window()
    for i, r in enumerate(rows):
        x = np.full((r, D), 0.01 * i, np.float32)
        eng.submit(x).result(timeout=60)
    rep = eng.report()
    eng.shutdown()
    padded = sum(_pow2(r) for r in rows)
    resident = transport == "inproc"
    for pn in rep.per_node:
        t = pn["totals"]
        inner_out = resident and pn["stage"] == 0
        inner_in = resident and pn["stage"] == 1
        assert t["compute_s"] == t["prefill_s"] + t["apply_s"] + t["d2h_s"]
        assert pn["compute_s"] == pytest.approx(t["compute_s"] / len(rows))
        assert t["prefill_n"] == 0 and t["apply_n"] == len(rows)
        assert (t["n"], t["waves"], t["rows"]) == (len(rows),) * 2 + (
            sum(rows),)
        assert t["prefills"] == t["step_rows"] == t["kv_bytes"] == 0
        assert t["h2d_bytes"] == (0 if inner_in else padded * D * 4)
        assert t["d2h_bytes"] == (0 if inner_out else padded * D * 4)
        assert (t["resident"], t["encodes"]) == (
            (len(rows), 0) if inner_out else (0, len(rows)))
        # an item that crossed a framing channel carries no enqueue
        # stamp: its wait is missing, not zero
        for h, framed in (("inbox", not resident), ("compute", False),
                          ("egress", False)):
            assert t[f"wait_{h}_n"] == (0 if framed else len(rows))
            assert t[f"wait_{h}_s"] >= 0
    d = rep.dispatcher
    assert d["serialize_n"] == d["collect_n"] == len(rows)
    for h in ("admission", "route0", "route1", "result"):
        framed = not resident and h != "admission"
        assert d[f"wait_{h}_n"] == (0 if framed else len(rows))
    assert rep.session["next_n"] == 0


@pytest.mark.parametrize("transport", ["inproc", "tcp"])
def test_decode_chain_totals_match_the_traffic(transport):
    """One session through a 2-stage decoder: one prefill per stage, a
    step row per further token, the session's slab slot gathered and
    written back once per step, bytes each way from the shapes, and the
    client's ``next`` spans (an argmax per token, a submit per step).  In
    process the activations between the stages stay on the device."""
    g = lm_graph()
    eng = InferenceEngine(g, TopologySpec.chain(g, 2, transport=transport),
                          RAW, max_batch=4)
    eng.configure(g.init(jax.random.PRNGKey(0)))
    prompt, m = [1, 5, 9, 2, 7], 6
    nodes = eng.dispatcher.nodes
    eng.start()
    eng.reset_window()
    it = eng.generate(prompt, m, session_id="s0")
    toks = [next(it)]
    kv = [n.kv_slot_bytes() for n in nodes]     # one slot of each slab
    toks += list(it)
    rep = eng.report()
    eng.shutdown()
    assert len(toks) == m
    d_model, vocab, p = 16, 32, len(prompt)
    steps = m - 1
    # (input, output) bytes per position: tokens in, activations between
    # the stages, logits out of the tail, and the positions a prefill
    # ships out: the whole prompt's activations, the last one's logits
    io = [(4, d_model * 4, p), (d_model * 4, vocab * 4, 1)]
    if transport == "inproc":       # the hop's activations: no copy
        io = [(4, 0, p), (0, vocab * 4, 1)]
    for pn, (i_b, o_b, out_p), kv_b in zip(rep.per_node, io, kv):
        t = pn["totals"]
        assert t["compute_s"] == t["prefill_s"] + t["apply_s"] + t["d2h_s"]
        assert (t["prefills"], t["prefill_n"]) == (1, 1)
        assert t["step_rows"] == t["apply_n"] == t["kv_gather_n"] == steps
        assert t["rows"] == 1 + steps
        assert t["n"] == 1 + steps + 1          # the open, steps, a close
        assert t["h2d_bytes"] == p * i_b + steps * (i_b + 4)   # + position
        assert t["d2h_bytes"] == out_p * o_b + steps * o_b
        assert t["kv_bytes"] == 2 * steps * kv_b > 0
    assert rep.session["next_n"] == m + steps


def test_reset_window_zeroes_every_total():
    g = mlp_graph(4)
    eng = InferenceEngine(g, 2, RAW, max_batch=4)
    eng.configure(g.init(jax.random.PRNGKey(0)))
    eng.submit(np.zeros((1, D), np.float32)).result(timeout=60)
    eng.dispatcher.drain()
    eng.reset_window()
    rep = eng.report()
    eng.shutdown()
    assert all(v == 0 for v in rep.dispatcher.values())
    for pn in rep.per_node:
        t = pn["totals"]
        assert all(t[k] == 0 for k in stage_stats(0).zero)


class _Closed:
    def recv(self):
        raise ChannelClosed("test")


def _snap(**vals) -> dict:
    snap = dict(stage_stats(1).zero, node=1, replica=0, epoch=2,
                inflight_n=3, batch_mean=99.0)
    snap.update(vals)
    return snap


def test_worker_handle_reports_deltas_of_the_worker_totals():
    """A worker's window is the difference of its running totals between
    heartbeats: batch_mean is requests over waves of the window, not the
    requests of one heartbeat interval."""
    h = WorkerHandle(None, 1, 0, None, _Closed(), 0, 0, 8, "t", None, None)
    h._relay_thread.join(5)
    h._on_hb({"snapshot": _snap(n=10, waves=4, apply_s=0.5, d2h_s=0.1,
                                kv_gather_s=0.2, wait_inbox_s=0.3,
                                wait_inbox_n=10, depth_sum=12,
                                depth_count=4)})
    h.reset_stats()
    h._on_hb({"snapshot": _snap(n=40, waves=10, apply_s=1.5, d2h_s=0.4,
                                prefill_s=0.25, kv_gather_s=0.6,
                                wait_inbox_s=0.9, wait_inbox_n=40,
                                depth_sum=30, depth_count=10)})
    snap = h.snapshot()
    assert (snap["n"], snap["waves"]) == (30, 6)
    assert snap["batch_mean"] == 30 / 6
    assert snap["apply_s"] == pytest.approx(1.0)
    assert snap["kv_gather_s"] == pytest.approx(0.4)
    assert snap["compute_s"] == pytest.approx(0.25 + 1.0 + 0.3)
    assert snap["wait_inbox_s"] == pytest.approx(0.6)
    assert snap["wait_inbox_n"] == 30
    assert snap["queue_depth_mean"] == pytest.approx(18 / 6)
    assert snap["depth_max"] == pytest.approx(3.0)
    assert (snap["epoch"], snap["inflight_n"]) == (2, 3)
