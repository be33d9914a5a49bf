"""Serving-time feedback controller: cost calibration from synthetic
traces, hysteresis, adaptive knobs, weighted admission / quotas, bucketed
pad-to-shape batching, and zero-loss live repartitioning under load."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import LayerGraph
from repro.runtime import (AdmissionFull, ControllerConfig, CostCalibrator,
                           InferenceEngine, TopologySpec, decide_repartition,
                           decide_scale, suggest_knobs)
from repro.runtime.dispatcher import (DispatcherCodecs,
                                      _WeightedAdmissionQueue)
from repro.runtime.node import _STOP
from repro.runtime.wire import WireCodec

D = 16

RAW = DispatcherCodecs(data=WireCodec("raw", "none"),
                       weights=WireCodec("raw", "none"))


def mlp_graph(depth: int = 8, d: int = D, rank3: bool = False) -> LayerGraph:
    shape = (1, 4, d) if rank3 else (1, d)
    g = LayerGraph("toy-mlp", jax.ShapeDtypeStruct(shape, np.float32))
    prev = ""
    for i in range(depth):
        g.layer(f"fc{i}",
                lambda p, x: jnp.tanh(x @ p["w"]),
                {"w": jax.ShapeDtypeStruct((d, d), np.float32)},
                (prev,),
                jax.ShapeDtypeStruct(shape, np.float32),
                flops=2.0 * d * d)
        prev = f"fc{i}"
    return g


def snap(node, n=16, compute_s=0.1, ser=0.01, des=0.01, mb=8, co=0.005,
         qd=1.0, bm=2.0):
    return {"node": node, "n": n, "compute_s": compute_s,
            "serialize_s": ser, "deserialize_s": des,
            "busy_decode_s": des, "busy_compute_s": compute_s,
            "busy_encode_s": ser, "queue_depth_mean": qd, "batch_mean": bm,
            "max_batch": mb, "coalesce_s": co, "payload_bytes": 0,
            "encodes": 1, "epoch": 0}


def sample(i: int, shape=(1, D)) -> np.ndarray:
    return np.random.default_rng(i).normal(size=shape).astype(np.float32)


# -- calibrator + decision (synthetic traces) --------------------------------

def test_skewed_compute_moves_predicted_cut():
    """Node 0 measures 3x the per-request compute of node 1: the
    calibrated DP moves the cut to shrink node 0's range."""
    g = mlp_graph(8)
    cal = CostCalibrator(g, alpha=1.0)
    cal.update([snap(0, compute_s=0.30 * 16 / 16),
                snap(1, compute_s=0.10)], [(0, 4), (4, 8)])
    assert cal.ready
    # measured per-layer time: node0's layers 3x node1's
    assert cal.layer_s[0] == pytest.approx(3 * cal.layer_s[4])
    dec = decide_repartition(cal.costs(), [0, 4, 8], 2, hysteresis=0.1)
    assert dec is not None
    assert dec["cuts"][0] < 4                  # fewer layers for node 0
    assert dec["predicted_new_s"] < dec["predicted_current_s"]


def test_hysteresis_holds_on_noisy_traces():
    """A few percent of imbalance (noise) must NOT trigger a migration."""
    g = mlp_graph(8)
    cal = CostCalibrator(g, alpha=1.0)
    cal.update([snap(0, compute_s=0.105), snap(1, compute_s=0.100)],
               [(0, 4), (4, 8)])
    assert decide_repartition(cal.costs(), [0, 4, 8], 2,
                              hysteresis=0.15) is None


def test_calibrator_not_ready_until_all_nodes_report():
    g = mlp_graph(8)
    cal = CostCalibrator(g)
    cal.update([snap(0), snap(1, n=0)], [(0, 4), (4, 8)])
    assert not cal.ready                       # node 1 had no traffic yet
    cal.update([snap(0), snap(1)], [(0, 4), (4, 8)])
    assert cal.ready


def test_ewma_converges_and_smooths():
    g = mlp_graph(4)
    cal = CostCalibrator(g, alpha=0.5)
    first = cal.layer_s.copy()
    for _ in range(12):
        cal.update([snap(0, compute_s=0.2)], [(0, 4)])
    per_layer = 0.2 / 16 / 4                   # per-request / layers
    assert np.allclose(cal.layer_s, per_layer, rtol=0.02)
    assert not np.allclose(first, cal.layer_s)


def test_suggest_knobs_codec_vs_compute_bound():
    codec_bound = snap(0, compute_s=0.05, ser=0.5, des=0.4, qd=6.0, bm=5.0)
    mb, co = suggest_knobs(codec_bound, cap=16)
    assert co > codec_bound["coalesce_s"]      # grow the coalescing window
    assert mb > codec_bound["max_batch"]       # backlogged: grow batches
    compute_bound = snap(0, compute_s=0.5, ser=0.01, des=0.01, qd=0.2,
                         bm=1.0)
    mb2, co2 = suggest_knobs(compute_bound, cap=16)
    assert co2 < compute_bound["coalesce_s"]   # shrink toward low latency
    assert mb2 < compute_bound["max_batch"]
    # clamps hold at the extremes (backlogged codec-bound node at the cap)
    lo, hi = 0.0005, 0.04
    s = snap(0, compute_s=0.01, ser=1.0, des=1.0, co=hi, qd=6.0, bm=2.0)
    assert suggest_knobs(s, cap=16, coalesce_bounds=(lo, hi))[1] == hi
    # no backlog: a codec-bound node still SHRINKS its window (coalescing
    # a trickle only adds latency, amortizes nothing)
    s = snap(0, compute_s=0.01, ser=1.0, des=1.0, co=0.01, qd=0.5, bm=1.0)
    assert suggest_knobs(s, cap=16)[1] < 0.01
    # the window never grows past the measured per-wave service time
    s = snap(0, n=16, compute_s=0.001, ser=0.008, des=0.008, co=0.005,
             qd=6.0, bm=2.0)
    wave_service = (0.001 + 0.016) / (16 / 2)
    assert suggest_knobs(s, cap=16)[1] <= wave_service
    # fully saturated codec-bound node (every wave FULL): max_batch still
    # grows toward the cap even though the coalesce branch is inactive
    s = snap(0, compute_s=0.05, ser=0.5, des=0.4, qd=8.0, bm=8.0, mb=8)
    mb3, co3 = suggest_knobs(s, cap=32)
    assert mb3 == 16 and co3 == s["coalesce_s"]


# -- weighted admission queue + quotas ---------------------------------------

def test_weighted_dequeue_proportional_no_starvation():
    q = _WeightedAdmissionQueue(64)
    for i in range(10):
        q.put(("p0", i), priority=0)
        q.put(("p1", i), priority=1)
    first9 = [q.get()[0] for _ in range(9)]
    # weight 2:1 — priority 1 gets ~2/3 of dequeues while both backlogged
    assert first9.count("p1") == 6 and first9.count("p0") == 3
    # FIFO within a band
    p1_idx = [item[1] for item in
              ([("p1", i) for i in range(10)])]
    assert p1_idx == sorted(p1_idx)
    rest = [q.get() for _ in range(11)]
    assert len(rest) == 11                     # nothing lost


def test_stop_never_overtakes_queued_requests():
    q = _WeightedAdmissionQueue(8)
    q.put("a", priority=0)
    q.put("b", priority=5)
    q.put(_STOP)
    assert q.get() is not _STOP
    assert q.get() is not _STOP
    assert q.get() is _STOP                    # surfaced only when drained


def test_client_quota_enforced_and_released():
    g = mlp_graph(6)
    params = g.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(g, 2, RAW, max_batch=2, client_quota=3)
    eng.configure(params)
    gate = threading.Event()
    node0 = eng.dispatcher.nodes[0]
    orig = node0._apply
    node0._apply = lambda b: (gate.wait(timeout=60), orig(b))[1]
    futs = [eng.submit(sample(i), client_id="greedy") for i in range(3)]
    with pytest.raises(AdmissionFull, match="quota"):
        eng.submit(sample(9), client_id="greedy")
    # another client is unaffected by the greedy one's quota
    other = eng.submit(sample(10), client_id="polite")
    gate.set()
    for f in futs + [other]:
        f.result(timeout=60)
    # quota released: the greedy client can admit again
    eng.submit(sample(11), client_id="greedy").result(timeout=60)
    eng.shutdown()


def test_priority_submit_end_to_end():
    g = mlp_graph(6)
    params = g.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(g, 2, RAW, max_batch=4)
    eng.configure(params)
    futs = [eng.submit(sample(i), client_id=i % 2, priority=i % 3)
            for i in range(9)]
    for i, f in enumerate(futs):
        ref = np.asarray(g.apply(params, jnp.asarray(sample(i))))
        np.testing.assert_allclose(f.result(timeout=60), ref, atol=1e-5)
    eng.shutdown()


# -- bucketed pad-to-shape (heterogeneous trailing shapes) -------------------

def _hand_offs(snap: dict, transport: str) -> int:
    """A replica's envelopes handed downstream, all of one kind: encoded
    over tcp, on the device (resident) in process."""
    on, off = (("resident", "encodes") if transport == "inproc"
               else ("encodes", "resident"))
    assert snap[off] == 0
    return snap[on]


def _framed_stage1(d, stage, replica):
    """Stage 1's replica behind a tcp inbox: stage 0's hop frames, while
    stage 0 itself is fed as in process (its waves form alike)."""
    if stage != 1:
        return None
    node = d._make_node(stage, replica)
    node.inbox = d._open_channel("tcp", 8)
    return node


@pytest.mark.parametrize("transport", ["inproc", "tcp"])
def test_pow2_buckets_merge_near_miss_shapes(transport):
    """(1, 5, D) and (1, 7, D) pad to (1, 8, D), merge into ONE apply and
    ONE hand-off, and come back trimmed to their original shapes with
    per-request reference numerics."""
    g = mlp_graph(6, rank3=True)
    params = g.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(g, TopologySpec.chain(g, 2), RAW, max_batch=8,
                          shape_buckets="pow2",
                          replica_factory=(_framed_stage1 if transport == "tcp"
                                           else None))
    eng.configure(params)
    gate = threading.Event()
    node0 = eng.dispatcher.nodes[0]
    orig = node0._apply
    node0._apply = lambda b: (gate.wait(timeout=60), orig(b))[1]
    xs = [sample(1, (1, 5, D)), sample(2, (1, 7, D))]
    futs = [eng.submit(x) for x in xs]
    deadline = time.perf_counter() + 10
    while node0._to_compute.qsize() < 1 and time.perf_counter() < deadline:
        time.sleep(0.01)
    time.sleep(0.2)
    gate.set()
    outs = [f.result(timeout=60) for f in futs]
    eng.shutdown()
    for x, out in zip(xs, outs):
        assert out.shape == x.shape            # trimmed back, not padded
        ref = np.asarray(g.apply(params, jnp.asarray(x)))
        np.testing.assert_allclose(out, ref, atol=1e-5)
    snap = node0.snapshot()
    assert snap["n"] == 2 and snap["waves"] == 1
    assert _hand_offs(snap, transport) == 1        # one bucket, one pass


@pytest.mark.parametrize("transport", ["inproc", "tcp"])
def test_exact_buckets_keep_shapes_separate(transport):
    """Default mode: near-miss shapes stay in their own buckets."""
    g = mlp_graph(4, rank3=True)
    params = g.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(g, TopologySpec.chain(g, 2, transport=transport),
                          RAW, max_batch=8)
    eng.configure(params)
    gate = threading.Event()
    node0 = eng.dispatcher.nodes[0]
    orig = node0._apply
    node0._apply = lambda b: (gate.wait(timeout=60), orig(b))[1]
    futs = [eng.submit(sample(1, (1, 5, D))), eng.submit(sample(2, (1, 7, D)))]
    time.sleep(0.2)
    gate.set()
    for f in futs:
        f.result(timeout=60)
    eng.shutdown()
    snap = node0.snapshot()
    assert snap["n"] == 2 and _hand_offs(snap, transport) == snap["n"]


# -- live repartition: zero loss, FIFO preserved -----------------------------

def test_live_repartition_zero_loss_fifo_under_load():
    """Two hot repartitions while client threads stream: every request
    resolves with reference numerics, per-client FIFO holds, and the
    chain's threads survive."""
    g = mlp_graph(8)
    params = g.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(g, TopologySpec.chain(g, 3, cuts=(5, 7)), RAW,
                          max_batch=4)
    eng.configure(params)
    eng.start()
    per_client, n_clients = 14, 3
    results: dict[int, list] = {}
    errors: list = []

    def client(c):
        try:
            xs = [sample(100 * c + i) for i in range(per_client)]
            results[c] = list(eng.submit_stream(xs, client_id=c))
        except Exception as e:                  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(n_clients)]
    for t in threads:
        t.start()
    rec1 = eng.dispatcher.reconfigure((3, 6))
    rec2 = eng.dispatcher.reconfigure((2, 4))
    for t in threads:
        t.join()
    rep = eng.report()
    eng.shutdown()
    assert not errors
    assert rec1["changed"] and rec1["acknowledged"]
    assert rec2["changed"] and rec2["acknowledged"]
    assert rep.epoch == 2 and rep.cuts == (2, 4)
    # zero loss + per-client FIFO: result i is exactly input i's output
    for c in range(n_clients):
        assert len(results[c]) == per_client
        for i, got in enumerate(results[c]):
            ref = np.asarray(g.apply(params, jnp.asarray(sample(100 * c + i))))
            np.testing.assert_allclose(got, ref, atol=1e-5)


def test_reconfigure_ships_only_weight_diff():
    """A one-layer boundary shift ships ~one layer of weights, not the
    whole model."""
    g = mlp_graph(8)
    params = g.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(g, 2, RAW, max_batch=2)
    eng.configure(params)
    eng.start()
    one_layer = D * D * 4
    rec = eng.dispatcher.reconfigure((3,))     # (0,4),(4,8) -> (0,3),(3,8)
    eng.shutdown()
    assert rec["moved_layers"] == 1
    assert one_layer <= rec["shipped_bytes"] <= 3 * one_layer


def test_reconfigure_across_paramless_layers():
    """CNN-style graphs interleave param-less layers (pool / add /
    activation): they produce no wire weights, and a migration across
    them must still commit (regression: the weight-diff check used to
    demand an entry for every layer and killed the compute thread)."""
    g = LayerGraph("mixed", jax.ShapeDtypeStruct((1, D), np.float32))
    prev = ""
    for i in range(8):
        if i % 2:
            g.layer(f"relu{i}", lambda p, x: jnp.maximum(x, 0.0), {},
                    (prev,), jax.ShapeDtypeStruct((1, D), np.float32),
                    flops=float(D))
            prev = f"relu{i}"
        else:
            g.layer(f"fc{i}",
                    lambda p, x: jnp.tanh(x @ p["w"]),
                    {"w": jax.ShapeDtypeStruct((D, D), np.float32)},
                    (prev,), jax.ShapeDtypeStruct((1, D), np.float32),
                    flops=2.0 * D * D)
            prev = f"fc{i}"
    params = g.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(g, 2, RAW, max_batch=2)
    eng.configure(params)
    eng.start()
    rec = eng.dispatcher.reconfigure((3,))     # boundary lands on relu3
    assert rec["changed"] and rec["acknowledged"]
    out = eng.submit(sample(5)).result(timeout=60)
    ref = np.asarray(g.apply(params, jnp.asarray(sample(5))))
    np.testing.assert_allclose(out, ref, atol=1e-5)
    for node in eng.dispatcher.nodes:
        assert all(t.is_alive() for t in node._threads)
    eng.shutdown()


def test_reconfigure_noop_and_validation():
    g = mlp_graph(8)
    params = g.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(g, 2, RAW)
    eng.configure(params)
    eng.start()
    assert eng.dispatcher.reconfigure((4,))["changed"] is False
    with pytest.raises(ValueError):
        eng.dispatcher.reconfigure((2, 5))     # wrong stage count
    eng.shutdown()


# -- controller closes the loop on a real chain ------------------------------

def test_controller_migrates_off_slow_node_and_keeps_serving():
    """Make node 0 artificially slow, drive controller steps under load:
    it must calibrate, migrate layers off node 0 (epoch advances), and
    every request before/during/after must resolve correctly."""
    g = mlp_graph(9)
    params = g.init(jax.random.PRNGKey(0))
    cfg = ControllerConfig(interval_s=30.0, ewma_alpha=1.0, hysteresis=0.05,
                           min_requests=8, cooldown_s=0.0,
                           precompile_after_swap=False)
    eng = InferenceEngine(g, 3, RAW, max_batch=4, controller=cfg)
    eng.configure(params)
    eng.start()                                # controller thread idles (30s)
    node0 = eng.dispatcher.nodes[0]
    orig = node0._apply
    node0._apply = lambda b: (time.sleep(0.05), orig(b))[1]
    futs = [eng.submit(sample(i), client_id=i % 2) for i in range(12)]
    for f in futs:
        f.result(timeout=60)
    action = eng.controller.step()             # deterministic control period
    assert action.kind == "repartition", action
    assert action.detail["acknowledged"]
    assert eng.dispatcher.partition.ranges()[0][1] < 3   # node 0 shrank
    # chain keeps serving correctly after the swap (the slow wrapper was
    # replaced by the migrated partition's fresh apply)
    futs = [eng.submit(sample(100 + i)) for i in range(6)]
    for i, f in enumerate(futs):
        ref = np.asarray(g.apply(params, jnp.asarray(sample(100 + i))))
        np.testing.assert_allclose(f.result(timeout=60), ref, atol=1e-5)
    rep = eng.report()
    eng.shutdown()
    assert rep.epoch == 1
    assert eng.controller.migrations == 1


def test_controller_holds_on_balanced_chain():
    """On a cost-balanced chain the deadband keeps the cuts put.  Tiny
    windows on tiny layers are noisy, so this uses a wide hysteresis —
    the tight-threshold semantics are covered synthetically above."""
    g = mlp_graph(9)
    params = g.init(jax.random.PRNGKey(0))
    cfg = ControllerConfig(interval_s=30.0, min_requests=4, hysteresis=0.75,
                           cooldown_s=0.0, adapt_knobs=False)
    eng = InferenceEngine(g, 3, RAW, max_batch=4, controller=cfg)
    eng.configure(params)
    eng.start()
    for i in range(8):
        eng.submit(sample(i)).result(timeout=60)
    action = eng.controller.step()
    eng.shutdown()
    assert action.kind == "hold"
    assert eng.controller.migrations == 0


def test_report_raw_utilization_unclamped():
    """util_*_raw report busy/wall honestly, unclamped (they can exceed
    1.0 on an oversubscribed host): every stage of a replica divides its
    busy seconds by the same window."""
    g = mlp_graph(6)
    params = g.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(g, 2, RAW, max_batch=4)
    eng.configure(params)
    _, rep = eng.run([sample(i) for i in range(6)])
    eng.shutdown()
    for pn in rep.per_node:
        walls = set()
        for stage in ("decode", "compute", "encode"):
            raw, busy = pn[f"util_{stage}_raw"], pn[f"busy_{stage}_s"]
            assert busy > 0.0 and raw > 0.0
            walls.add(round(busy / raw, 9))
        assert len(walls) == 1
        assert "utilization" not in pn and "util_compute" not in pn
        assert pn["max_batch"] >= 1 and pn["coalesce_s"] >= 0.0
