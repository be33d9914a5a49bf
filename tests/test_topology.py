"""Topology-first serving: TopologySpec validation, replicated-stage
routing + FIFO-per-client ordering (the sequenced merge), elastic
membership (spawn/drain under load with zero loss), pluggable transports,
and per-layer pad-safety."""
import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import LayerGraph
from repro.runtime import (ControllerConfig, InferenceEngine, StageSpec,
                           TopologySpec, decide_scale, register_transport)
from repro.runtime.dispatcher import DispatcherCodecs
from repro.runtime.transport import InprocChannel, InprocTransport, Transport
from repro.runtime.wire import WireCodec

D = 16

RAW = DispatcherCodecs(data=WireCodec("raw", "none"),
                       weights=WireCodec("raw", "none"))


def mlp_graph(depth: int = 6, d: int = D, rank3: bool = False,
              unsafe: set | None = None) -> LayerGraph:
    shape = (1, 4, d) if rank3 else (1, d)
    g = LayerGraph("toy-mlp", jax.ShapeDtypeStruct(shape, np.float32))
    prev = ""
    for i in range(depth):
        g.layer(f"fc{i}",
                lambda p, x: jnp.tanh(x @ p["w"]),
                {"w": jax.ShapeDtypeStruct((d, d), np.float32)},
                (prev,),
                jax.ShapeDtypeStruct(shape, np.float32),
                flops=2.0 * d * d,
                pad_safe=i not in (unsafe or set()))
        prev = f"fc{i}"
    return g


def sample(i: int, shape=(1, D)) -> np.ndarray:
    return np.random.default_rng(i).normal(size=shape).astype(np.float32)


def make_engine(topology, graph=None, **kw):
    g = graph if graph is not None else mlp_graph()
    params = g.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(g, topology, RAW, **kw)
    eng.configure(params)
    return g, params, eng


# -- TopologySpec -------------------------------------------------------------

def test_spec_validation():
    g = mlp_graph(6)
    spec = TopologySpec.chain(g, 3)
    spec.validate(g)
    assert spec.bounds == [0, 2, 4, 6] and spec.replicas == (1, 1, 1)
    assert spec.with_replicas(1, 3).replicas == (1, 3, 1)
    assert spec.with_layers([0, 1, 2, 6]).cuts == (1, 2)
    with pytest.raises(ValueError):          # hole in the coverage
        TopologySpec((StageSpec((0, 2)), StageSpec((3, 6)))).validate(g)
    with pytest.raises(ValueError):          # doesn't reach the last layer
        TopologySpec((StageSpec((0, 4)),)).validate(g)
    with pytest.raises(ValueError):
        TopologySpec((StageSpec((0, 6), replicas=0),)).validate(g)
    with pytest.raises(ValueError):
        TopologySpec((StageSpec((0, 6), routing="zigzag"),)).validate(g)
    with pytest.raises(ValueError):
        TopologySpec((StageSpec((0, 6), transport="carrier-pigeon"),)
                     ).validate(g)
    with pytest.raises(ValueError):          # wrong per-stage replica list
        TopologySpec.chain(g, 3, replicas=[2, 2])
    assert TopologySpec.chain(g, 2, replicas=2).replicas == (2, 2)
    assert TopologySpec.chain(g, 2, cuts=(5,)).bounds == [0, 5, 6]


def test_engine_accepts_int_as_chain_sugar():
    g, params, eng = make_engine(3)
    assert eng.topology.num_stages == 3
    assert eng.dispatcher.replicas == (1, 1, 1)
    out = eng.submit(sample(0)).result(timeout=60)
    np.testing.assert_allclose(
        out, np.asarray(g.apply(params, jnp.asarray(sample(0)))), atol=1e-5)
    eng.shutdown()


# -- replicated stages: ordering is the contract ------------------------------

def test_replicated_stage_fifo_per_client_random_delays():
    """Property-style: a 3-replica middle stage whose replicas each sleep
    a different random time per batch WILL complete batches out of order;
    every client must still see its own results in submission order —
    asserted on the actual future resolution order (the sequenced merge),
    not just on stream()'s await order — with reference numerics."""
    spec = TopologySpec.chain(mlp_graph(), 3).with_replicas(1, 3)
    g, params, eng = make_engine(spec, max_batch=2)
    eng.start()
    mid = eng.dispatcher.stages[1].replicas
    assert len(mid) == 3
    for k, node in enumerate(mid):           # deterministic, replica-skewed
        rng = np.random.default_rng(k)       # delays out-of-order the chain
        orig = node._apply
        node._apply = (lambda b, o=orig, r=rng, k=k:
                       (time.sleep(float(r.uniform(0.001, 0.02 * (k + 1)))),
                        o(b))[1])
    n_clients, per_client = 4, 12
    resolved: dict[int, list] = {c: [] for c in range(n_clients)}
    res_lock = threading.Lock()
    futs: dict[int, list] = {c: [] for c in range(n_clients)}
    for i in range(per_client):              # interleave clients' submits
        for c in range(n_clients):
            f = eng.submit(sample(100 * c + i), client_id=c)
            f.add_done_callback(
                lambda _, c=c, i=i: (res_lock.acquire(),
                                     resolved[c].append(i),
                                     res_lock.release()))
            futs[c].append(f)
    for c in range(n_clients):
        for i, f in enumerate(futs[c]):
            ref = np.asarray(g.apply(params, jnp.asarray(sample(100 * c + i))))
            np.testing.assert_allclose(f.result(timeout=60), ref, atol=1e-5)
    eng.shutdown()
    # zero lost, zero duplicated, zero reordered — per client
    for c in range(n_clients):
        assert resolved[c] == list(range(per_client)), resolved[c]
    # the replicas genuinely shared the stage's work
    served = [node.snapshot()["n"] for node in mid]
    assert sum(served) == n_clients * per_client
    assert sum(1 for s in served if s > 0) >= 2, served


def test_replicated_routing_round_robin():
    spec = TopologySpec.chain(mlp_graph(), 2, routing="rr").with_replicas(
        1, 3)
    g, params, eng = make_engine(spec, max_batch=1)
    eng.start()
    futs = [eng.submit(sample(i)) for i in range(9)]
    for f in futs:
        f.result(timeout=60)
    eng.dispatcher.drain()
    served = [node.snapshot()["n"]
              for node in eng.dispatcher.stages[1].replicas]
    eng.shutdown()
    assert sum(served) == 9
    assert all(s >= 1 for s in served), served   # rr touches every replica


# -- elastic membership -------------------------------------------------------

def _stream_clients(eng, g, params, n_clients, per_client, base=0):
    results: dict[int, list] = {}
    errors: list = []

    def client(c):
        try:
            xs = [sample(base + 100 * c + i) for i in range(per_client)]
            results[c] = list(eng.submit_stream(xs, client_id=c))
        except Exception as e:                  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(n_clients)]
    for t in threads:
        t.start()
    return threads, results, errors


def _check_streams(g, params, results, errors, n_clients, per_client,
                   base=0):
    assert not errors, errors
    for c in range(n_clients):
        assert len(results[c]) == per_client   # zero lost, zero duplicated
        for i, got in enumerate(results[c]):   # zero reordered: result i is
            ref = np.asarray(g.apply(            # exactly input i's output
                params, jnp.asarray(sample(base + 100 * c + i))))
            np.testing.assert_allclose(got, ref, atol=1e-5)


def test_scale_up_under_load_zero_loss():
    """1 -> 3 replicas on the middle stage while clients stream: nothing
    lost/duplicated/reordered, and the spawned replicas take real work."""
    g = mlp_graph(8)
    g, params, eng = make_engine(TopologySpec.chain(g, 3), graph=g,
                                 max_batch=2)
    eng.start()
    threads, results, errors = _stream_clients(eng, g, params, 3, 16)
    rec = eng.scale(1, 3)
    for t in threads:
        t.join()
    # keep serving after the fence so spawned replicas demonstrably work
    threads, r2, e2 = _stream_clients(eng, g, params, 3, 8, base=5000)
    for t in threads:
        t.join()
    served = [node.snapshot()["n"]
              for node in eng.dispatcher.stages[1].replicas]
    rep = eng.report()
    eng.shutdown()
    assert rec["changed"] and rec["spawned"] == 2
    assert rec["shipped_bytes"] > 0            # stage weights went over wire
    _check_streams(g, params, results, errors, 3, 16)
    _check_streams(g, params, r2, e2, 3, 8, base=5000)
    assert rep.replicas == (1, 3, 1) and rep.epoch == 1
    assert sum(1 for s in served if s > 0) >= 2, served


def test_drain_under_load_zero_loss():
    """3 -> 1 replicas on the middle stage while clients stream: the
    drained replicas flush everything already routed to them, their
    threads exit, and no response is lost, duplicated, or reordered."""
    g = mlp_graph(8)
    spec = TopologySpec.chain(g, 3).with_replicas(1, 3)
    g, params, eng = make_engine(spec, graph=g, max_batch=2)
    eng.start()
    before = list(eng.dispatcher.stages[1].replicas)
    threads, results, errors = _stream_clients(eng, g, params, 3, 16)
    time.sleep(0.05)                           # mid-stream drain
    rec = eng.scale(1, 1)
    for t in threads:
        t.join()
    threads, r2, e2 = _stream_clients(eng, g, params, 3, 8, base=7000)
    for t in threads:
        t.join()
    rep = eng.report()
    eng.shutdown()
    assert rec["changed"] and rec["retired"] == 2 and rec["acknowledged"]
    _check_streams(g, params, results, errors, 3, 16)
    _check_streams(g, params, r2, e2, 3, 8, base=7000)
    assert rep.replicas == (1, 1, 1) and rep.epoch == 1
    retired = [n for n in before
               if n not in eng.dispatcher.stages[1].replicas]
    assert len(retired) == 2
    for node in retired:                       # flushed and exited cleanly
        assert not any(t.is_alive() for t in node._threads)


def _drain_fence_shutdown_race(scale_stage: int):
    """shutdown() while a drain fence is still stuck behind the draining
    replica's gated backlog: the last LIVE stop reaches the downstream
    consumer before the straggler's fence copy lowers the stop
    expectation (the drained replica never stops), so the consumer must
    re-check after the barrier — without that, the router (mid-stage leg)
    or collector (tail leg) blocks forever and shutdown deadlocks."""
    g = mlp_graph(6)
    spec = TopologySpec.chain(g, 2, routing="rr").with_replicas(
        scale_stage, 2)
    g, params, eng = make_engine(spec, graph=g, max_batch=1)
    eng.start()
    victim = eng.dispatcher.stages[scale_stage].replicas[1]
    gate = threading.Event()
    entered = threading.Event()
    orig = victim._apply

    def gated(b):
        entered.set()
        gate.wait(timeout=60)
        return orig(b)

    victim._apply = gated
    futs = [eng.submit(sample(i)) for i in range(4)]   # rr: victim holds work
    # the fence is injected directly into the head channel, so it can
    # overtake envelopes still in the admission queue: wait until the
    # victim provably holds PRE-fence work, or the fence clears instantly
    assert entered.wait(timeout=60)
    rec = eng.scale(scale_stage, 1, timeout=0.05)      # fence stuck in flight
    assert rec["changed"] and not rec["acknowledged"]
    done = threading.Event()
    t = threading.Thread(
        target=lambda: (eng.shutdown(drain=False), done.set()))
    t.start()
    time.sleep(0.3)              # let _STOP chase the fence into the chain
    gate.set()
    assert done.wait(timeout=60), "shutdown deadlocked behind drain fence"
    t.join()
    for i, f in enumerate(futs):                       # nothing was lost
        ref = np.asarray(g.apply(params, jnp.asarray(sample(i))))
        np.testing.assert_allclose(f.result(timeout=5), ref, atol=1e-5)


def test_shutdown_races_drain_fence_at_collector():
    _drain_fence_shutdown_race(scale_stage=1)          # tail -> collector


def test_shutdown_races_drain_fence_at_midstage_router():
    _drain_fence_shutdown_race(scale_stage=0)          # -> stage-1 router


def test_unacked_drain_retiree_visible_then_pruned():
    """An un-acked drain keeps the still-flushing replica visible (its
    telemetry is real), but once its threads exit it must be pruned at
    the next membership read — a dead retiree's frozen snapshot epoch
    would otherwise make the controller rebaseline forever."""
    g = mlp_graph(6)
    spec = TopologySpec.chain(g, 2, routing="rr").with_replicas(1, 2)
    g, params, eng = make_engine(spec, graph=g, max_batch=1)
    eng.start()
    victim = eng.dispatcher.stages[1].replicas[1]
    gate = threading.Event()
    entered = threading.Event()
    orig = victim._apply

    def gated(b):
        entered.set()
        gate.wait(timeout=60)
        return orig(b)

    victim._apply = gated
    futs = [eng.submit(sample(i)) for i in range(4)]
    assert entered.wait(timeout=60)           # victim holds pre-fence work
    rec = eng.scale(1, 1, timeout=0.05)
    assert rec["changed"] and not rec["acknowledged"]
    assert victim.retiring
    assert len(eng.dispatcher.stages[1].replicas) == 2   # still flushing
    gate.set()
    for i, f in enumerate(futs):              # zero loss through it all
        ref = np.asarray(g.apply(params, jnp.asarray(sample(i))))
        np.testing.assert_allclose(f.result(timeout=60), ref, atol=1e-5)
    deadline = time.perf_counter() + 30
    while any(t.is_alive() for t in victim._threads) \
            and time.perf_counter() < deadline:
        time.sleep(0.01)
    assert eng.dispatcher.replicas == (1, 1)  # pruned at the read
    assert victim not in eng.dispatcher.stages[1].replicas
    eng.shutdown()


def test_scale_validation_and_noop():
    g, params, eng = make_engine(2)
    eng.start()
    assert eng.scale(0, 1)["changed"] is False
    with pytest.raises(ValueError):
        eng.scale(0, 0)
    with pytest.raises(ValueError):
        eng.scale(7, 2)
    eng.shutdown()


def test_scale_then_repartition_composes():
    """A replicated stage and a later cut migration coexist: all replicas
    of the scaled stage adopt the new boundaries at the fence."""
    g = mlp_graph(8)
    g, params, eng = make_engine(TopologySpec.chain(g, 2), graph=g,
                                 max_batch=2)
    eng.start()
    eng.scale(1, 2)
    rec = eng.dispatcher.reconfigure((3,))
    futs = [eng.submit(sample(i)) for i in range(8)]
    for i, f in enumerate(futs):
        ref = np.asarray(g.apply(params, jnp.asarray(sample(i))))
        np.testing.assert_allclose(f.result(timeout=60), ref, atol=1e-5)
    eng.shutdown()
    assert rec["changed"] and rec["acknowledged"]
    for node in eng.dispatcher.stages[1].replicas:
        assert node.epoch == 2                # both fences committed
        assert [n.name for n in node._nodes] == [f"fc{i}"
                                                for i in range(3, 8)]
    # the diff shipped once per replica of the resized stage
    assert eng.dispatcher.replicas == (1, 2)


# -- controller's replica dimension -------------------------------------------

def test_decide_scale_up_and_down():
    from repro.core.partitioner import CalibratedCosts
    costs = CalibratedCosts(
        layer_s=np.array([0.1, 0.8, 0.1]), cut_bytes=np.full(3, 4.0),
        head_in_bytes=4.0, tail_out_bytes=4.0)
    # one layer per stage: cuts have no freedom, replicas are the lever
    rec = decide_scale(costs, [0, 1, 2, 3], [1, 1, 1])
    assert rec == {**rec, "stage": 1, "replicas": 2, "direction": "up"}
    # at the ceiling: no recommendation
    assert decide_scale(costs, [0, 1, 2, 3], [1, 4, 1],
                        max_replicas=4) is None
    # an over-provisioned cold stage sheds a replica
    rec = decide_scale(costs, [0, 1, 2, 3], [4, 4, 1])
    assert rec["stage"] == 0 and rec["replicas"] == 3
    assert rec["direction"] == "down"
    # single-stage topology: no runner-up means no measured imbalance —
    # must NOT recommend an unconditional spawn on an idle engine
    assert decide_scale(costs, [0, 3], [1]) is None


def test_controller_scales_unsplittable_bottleneck():
    """One layer per stage (cuts frozen by construction), middle stage
    artificially slow: the repartition arm must hold and the scale arm
    must grow the bottleneck stage — executed live, zero loss."""
    g = mlp_graph(3)
    cfg = ControllerConfig(interval_s=30.0, ewma_alpha=1.0, min_requests=8,
                           cooldown_s=0.0, hysteresis=0.05,
                           replica_scaling=True, execute_scaling=True,
                           precompile_after_swap=False)
    spec = TopologySpec.chain(g, 3)
    g, params, eng = make_engine(spec, graph=g, max_batch=2, controller=cfg)
    eng.start()                                # 30s interval: idle thread
    node = eng.dispatcher.stages[1].replicas[0]
    orig = node._apply
    node._apply = lambda b: (time.sleep(0.03), orig(b))[1]
    futs = [eng.submit(sample(i), client_id=i % 2) for i in range(12)]
    for f in futs:
        f.result(timeout=60)
    action = eng.controller.step()
    assert action.kind == "scale", action
    assert action.detail["stage"] == 1 and action.detail["direction"] == "up"
    assert action.detail["acknowledged"]
    assert eng.dispatcher.replicas == (1, 2, 1)
    futs = [eng.submit(sample(100 + i)) for i in range(8)]
    for i, f in enumerate(futs):
        ref = np.asarray(g.apply(params, jnp.asarray(sample(100 + i))))
        np.testing.assert_allclose(f.result(timeout=60), ref, atol=1e-5)
    eng.shutdown()
    assert eng.controller.migrations == 1


def test_controller_recommends_without_executing():
    g = mlp_graph(3)
    cfg = ControllerConfig(interval_s=30.0, ewma_alpha=1.0, min_requests=8,
                           cooldown_s=0.0, hysteresis=0.05,
                           replica_scaling=True, execute_scaling=False,
                           adapt_knobs=False)
    g, params, eng = make_engine(TopologySpec.chain(g, 3), graph=g,
                                 max_batch=2, controller=cfg)
    eng.start()
    node = eng.dispatcher.stages[1].replicas[0]
    orig = node._apply
    node._apply = lambda b: (time.sleep(0.03), orig(b))[1]
    for i in range(10):
        eng.submit(sample(i)).result(timeout=60)
    action = eng.controller.step()
    eng.shutdown()
    assert action.kind == "scale_recommend", action
    assert action.detail["stage"] == 1
    assert eng.dispatcher.replicas == (1, 1, 1)   # nothing executed


# -- pluggable transports -----------------------------------------------------

class _CountingChannel(InprocChannel):
    sends = 0

    def send(self, item):
        type(self).sends += 1
        super().send(item)


class _CountingTransport(Transport):
    name = "counting"

    def channel(self, capacity: int = 0):
        return _CountingChannel(capacity)


def test_custom_transport_carries_the_stage():
    register_transport("counting", _CountingTransport)
    _CountingChannel.sends = 0
    spec = TopologySpec.chain(mlp_graph(), 2, transport="counting")
    g, params, eng = make_engine(spec, max_batch=2)
    eng.start()
    futs = [eng.submit(sample(i)) for i in range(5)]
    for i, f in enumerate(futs):
        ref = np.asarray(g.apply(params, jnp.asarray(sample(i))))
        np.testing.assert_allclose(f.result(timeout=60), ref, atol=1e-5)
    eng.shutdown()
    # every hop (pump->router, router->replica, relay, tail) used the
    # registered backend, envelopes and stop tokens alike
    assert _CountingChannel.sends >= 5 * 3


def test_unknown_transport_rejected():
    spec = TopologySpec((StageSpec((0, 6), transport="udp?"),))
    with pytest.raises(ValueError, match="unknown transport"):
        make_engine(spec)


# -- per-layer pad safety -----------------------------------------------------

def _stalled_pair(eng, node, shapes):
    """Deterministically land ``shapes``' requests in ONE compute merge: a
    plug request provably occupies the gated apply first (so it cannot
    absorb them), the pair is decoded into the compute queue behind it,
    and the gate opens only once every pair extent is queued — the next
    merge then drains them together."""
    gate = threading.Event()
    entered = threading.Event()
    orig = node._apply

    def gated(b):
        entered.set()
        gate.wait(timeout=60)
        return orig(b)

    node._apply = gated
    plug = eng.submit(sample(39, (1, 3, D)))
    assert entered.wait(timeout=60)     # compute thread is inside apply
    futs = [eng.submit(sample(40 + i, s)) for i, s in enumerate(shapes)]

    def decoded_parts():                # pair extents decoded and queued
        return sum(len(d.extents) for w in list(node._to_compute.queue)
                   if isinstance(w, list) for d in w)

    deadline = time.perf_counter() + 10
    while decoded_parts() < len(shapes) and time.perf_counter() < deadline:
        time.sleep(0.01)
    assert decoded_parts() == len(shapes)
    gate.set()
    plug.result(timeout=60)
    return futs


def _hand_offs(snap: dict, transport: str) -> int:
    """A replica's envelopes handed downstream, all of one kind: encoded
    over tcp, on the device (resident) in process."""
    on, off = (("resident", "encodes") if transport == "inproc"
               else ("encodes", "resident"))
    assert snap[off] == 0
    return snap[on]


@pytest.mark.parametrize("transport", ["inproc", "tcp"])
def test_pad_unsafe_layer_falls_back_to_exact_buckets(transport):
    """A segment containing a pad-unsafe layer must NOT pow2-pad: the
    near-miss shapes stay in separate buckets (two hand-offs), numerics
    are exact, while a safe segment of the same graph still merges."""
    g = mlp_graph(6, rank3=True, unsafe={1})   # fc1 is stage 0's layer
    params = g.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(g, TopologySpec.chain(g, 2, cuts=(3,),
                                                transport=transport), RAW,
                          max_batch=8, shape_buckets="pow2")
    eng.configure(params)
    node0 = eng.dispatcher.stages[0].replicas[0]
    node1 = eng.dispatcher.stages[1].replicas[0]
    assert not node0._pad_safe and node1._pad_safe
    xs = [(1, 5, D), (1, 7, D)]
    futs = _stalled_pair(eng, node0, xs)
    outs = [f.result(timeout=60) for f in futs]
    eng.dispatcher.drain()
    eng.shutdown()
    for shape, out in zip(xs, outs):
        assert out.shape == shape
        ref = np.asarray(g.apply(params, jnp.asarray(sample(
            40 + xs.index(shape), shape))))
        np.testing.assert_allclose(out, ref, atol=1e-5)
    # unsafe segment: one hand-off PER REQUEST (no bucket merge)
    # (the plug's wave, then the pair's)
    snap0 = node0.snapshot()
    assert snap0["n"] == 3 and snap0["waves"] == 2
    assert _hand_offs(snap0, transport) == snap0["n"]


@pytest.mark.parametrize("transport", ["inproc", "tcp"])
def test_pad_safe_graph_still_merges(transport):
    g = mlp_graph(6, rank3=True)
    params = g.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(g, TopologySpec.chain(g, 2, transport=transport),
                          RAW, max_batch=8, shape_buckets="pow2")
    eng.configure(params)
    node0 = eng.dispatcher.stages[0].replicas[0]
    futs = _stalled_pair(eng, node0, [(1, 5, D), (1, 7, D)])
    for f in futs:
        f.result(timeout=60)
    eng.shutdown()
    # the plug's wave and hand-off, then the pair's in one bucket
    snap = node0.snapshot()
    assert snap["n"] == 3 and snap["waves"] == 2
    assert _hand_offs(snap, transport) == 2
