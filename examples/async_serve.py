"""Topology-first async serving: spec -> engine -> scale.

The serving API is declarative: a :class:`TopologySpec` says what the
chain IS — an ordered list of stages, each binding a contiguous layer
range to a replica count, a routing policy (round-robin /
least-queue-depth), a transport, and optional batching-knob overrides —
and the engine builds exactly that.  Many clients then share the topology
concurrently via ``submit()``/``stream()``, with a bounded admission
queue shedding load and a sequence-numbered merge keeping every client's
responses in its own submission order no matter how replicas reorder
batches in flight.

The walkthrough below:

1. **spec** — plan a 4-stage chain with the partitioner, then give the
   heaviest stage 2 replicas up front;
2. **engine** — configure (weights ship over the wire to every replica)
   and serve a burst of concurrent clients;
3. **scale** — grow the bottleneck stage to 3 replicas and drain it back
   to 1 on the RUNNING engine.  Both ride the epoch fence: spawned
   replicas receive the stage's weights and are fenced into the routing
   set; drained replicas are fenced out, flush their in-flight work, and
   retire.  Zero requests are dropped or reordered.

Controller knobs (the serving-time feedback loop)
-------------------------------------------------
Passing ``controller=ControllerConfig(...)`` turns the static topology
into a self-optimizing one.  Three independently gateable arms:

* ``repartition=True`` — every ``interval_s`` the controller folds the
  stages' measured per-stage timings into an EWMA cost model
  (``ewma_alpha``), re-runs the partition DP on those *calibrated* costs
  priced for the live replica counts, and — only when the predicted
  bottleneck improves by more than ``hysteresis`` — hot-migrates the
  cuts behind the same epoch fence.
* ``adapt_knobs=True`` — per stage, the measured codec/compute
  stage-time ratio retunes ``coalesce_s`` within ``coalesce_bounds`` and
  ``max_batch`` within [1, ``max_batch_cap``], uniformly across replicas.
* ``replica_scaling=True`` — when the calibrated DP says cuts CANNOT fix
  the bottleneck, the controller recommends a replica change for it
  (``scale_recommend`` actions); with ``execute_scaling=True`` it commits
  the change itself via the same ``scale()`` path demonstrated below.

Per-request QoS rides the same admission queue: ``submit(..., priority=p)``
weights the dequeue (band weight ``p + 1``, no starvation), and
``client_quota=n`` caps any one client's in-flight requests.

    PYTHONPATH=src python examples/async_serve.py
"""
import threading

import jax
import numpy as np

from repro.models import cnn
from repro.runtime import (AdmissionFull, ControllerConfig, InferenceEngine,
                           TopologySpec)
from repro.runtime.dispatcher import DispatcherCodecs
from repro.runtime.wire import WireCodec

STAGES, CLIENTS, PER_CLIENT = 4, 6, 4

graph = cnn.resnet50(batch=1, image=64, num_classes=10)
params = graph.init(jax.random.PRNGKey(0))

# 1. spec: the partitioner picks the cuts; the heaviest stage starts with
#    2 replicas (a hand-built spec could instead list explicit StageSpecs
#    with per-stage layer ranges, transports, and knob overrides).
#    transport="tcp" would put every hop on real loopback sockets, and
#    transport="link:10mbit,20ms" on the paper's emulated Ethernet — the
#    serving code below is identical either way
spec = TopologySpec.chain(graph, STAGES, strategy="balanced_latency")
heavy = max(range(STAGES),
            key=lambda i: spec.stages[i].layers[1] - spec.stages[i].layers[0])
spec = spec.with_replicas(heavy, 2)
print("topology:", " | ".join(
    f"stage {i}: layers {s.layers} x{s.replicas}"
    for i, s in enumerate(spec.stages)))

# 2. engine: build the declared topology and serve
engine = InferenceEngine(
    graph, spec,
    DispatcherCodecs(data=WireCodec("zfp", "none", zfp_rate=16),
                     weights=WireCodec("raw", "none")),
    max_batch=4, admission_depth=32,
    client_quota=2 * PER_CLIENT,           # no client monopolizes admission
    # close the measurement->plan loop.  min_requests is set above this
    # short demo's traffic so the run shows calibration + knob adaptation
    # without paying a live resnet migration (minutes of XLA recompiles on
    # a laptop CPU); benchmarks/serve_load.py --rebalance and --elastic
    # demonstrate the hot repartition and live replica scaling end to end
    # on serving-scale chains
    controller=ControllerConfig(
        interval_s=0.5, hysteresis=0.15, cooldown_s=5.0,
        min_requests=2 * CLIENTS * PER_CLIENT,
        replica_scaling=True))             # recommend-only (no execute)
engine.configure(params)
engine.start()


def client(c: int, out: dict) -> None:
    xs = [np.random.default_rng(100 * c + i)
          .normal(size=(1, 64, 64, 3)).astype(np.float32)
          for i in range(PER_CLIENT)]
    try:
        # stream() admits eagerly and yields THIS client's results FIFO —
        # the sequenced merge guarantees it even across the replicated
        # stage; the admission timeout turns overload into AdmissionFull
        out[c] = [int(np.argmax(y))
                  for y in engine.submit_stream(xs, client_id=c, timeout=60.0)]
    except AdmissionFull:
        out[c] = "shed"       # a real front-end would retry with backoff


results: dict = {}
threads = [threading.Thread(target=client, args=(c, results))
           for c in range(CLIENTS)]
for t in threads:
    t.start()
for t in threads:
    t.join()

# 3. scale: membership is live.  Grow the bottleneck stage, serve one more
#    client burst through the wider topology, then drain it back — the
#    epoch fence means no request in flight is dropped either way.
rec_up = engine.scale(heavy, 3)
print(f"scale stage {heavy} -> 3 replicas: spawned {rec_up['spawned']}, "
      f"{rec_up['shipped_bytes'] / 1e6:.1f} MB of weights shipped, "
      f"acked={rec_up['acknowledged']}")
more: dict = {}
threads = [threading.Thread(target=client, args=(c, more))
           for c in range(CLIENTS)]
for t in threads:
    t.start()
for t in threads:
    t.join()
rec_down = engine.scale(heavy, 1)
print(f"drain stage {heavy} -> 1 replica: retired {rec_down['retired']}, "
      f"acked={rec_down['acknowledged']}")

report = engine.report()
controller_log = list(engine.controller.actions)
engine.shutdown()

for c in sorted(results):
    print(f"client {c}: classes {results[c]} then {more.get(c)}")
print(f"\n{report.samples} requests over {report.num_nodes} replicas "
      f"({'x'.join(map(str, report.replicas))} per stage): "
      f"{report.throughput_cps:.1f} req/s, "
      f"p50 {report.p50_latency_s*1e3:.0f} ms, "
      f"p99 {report.p99_latency_s*1e3:.0f} ms")
for pn in report.per_node:
    print(f"  stage {pn['stage']} replica {pn['replica']}: "
          f"util dec/cmp/enc {pn['util_decode_raw']*100:4.1f}/"
          f"{pn['util_compute_raw']*100:4.1f}/"
          f"{pn['util_encode_raw']*100:4.1f}%  "
          f"mean batch {pn['batch_mean']:.2f}  "
          f"service {pn['service_s']*1e3:.2f} ms  "
          f"knobs mb={pn['max_batch']} co={pn['coalesce_s']*1e3:.1f}ms")
print(f"partition epoch {report.epoch}, cuts {report.cuts}; "
      f"controller decided: "
      f"{[a.kind for a in controller_log] or '(no full period elapsed)'}")
