"""Closed-loop multi-client load test: staged codec/compute-overlap runtime
vs the PR 1 baseline and the synchronous engine, on the same DEFER chain —
plus the PR 3 skewed-chain scenario where the serving-time controller
recalibrates costs online and hot-repartitions a mis-planned chain.

N concurrent clients each send M samples closed-loop (a client admits its
next request only after receiving the previous result).

Classic A/B (``run``):

* ``sync``     — the seed's serving model: blocking submit with ONE request
  in the chain at a time (global lock, max_batch=1), PR 1 codecs.
* ``async``    — the PR 1 async runtime, faithfully: continuous batching,
  but each node runs decode -> apply -> encode sequentially on one worker
  thread, re-encodes every request separately (``staged=False``), and uses
  the PR 1 codec implementations (``WireCodec(vectorized=False)``).
* ``staged``   — the PR 2 runtime: 3-stage per-node pipeline overlapping
  codec with compute, batch-level wire encoding, vectorized codecs.

Rebalance scenario (``run_rebalance``, PR 3): a chain whose first layers
are wide-FFN blocks, so the paper's ``equal_layers`` plan dumps ~all the
compute on node 0 — while the *balanced* plan gives the light-layer node
~3x the layers.  ``static`` serves on the equal_layers plan with fixed
knobs; ``controller`` starts from the SAME bad plan and lets the feedback
controller calibrate real costs, hot-migrate the cuts behind an epoch
fence (zero requests dropped), and adapt max_batch / coalesce_s online.

Elastic scenario (``run_elastic``, ISSUE 4): a 2-stage topology whose
stage 0 is a single widening layer that must ENCODE a 16x-wide activation
for the hop — with ZFP/LZ4 that encode saturates the stage (its egress
measures ~0.98 busy) while the decode side is ~6x cheaper, so stage 0 is
the bottleneck and the cut CANNOT move to fix it (one layer is already
minimal).  Replicas are the only lever: serving starts with 1 replica on
the bottleneck stage and ``Engine.scale()``s it to 2..N **under
closed-loop load** (the epoch fence keeps zero requests dropped —
asserted, every in-flight future must resolve); each membership is then
measured.  The codec is single-threaded per replica, so replication
parallelizes the wire encode — the honest in-process analogue of SEIFER
replicating a bottleneck partition across devices.  Results (throughput
before/after, dropped counts) land in BENCH_elastic.json.

Acceptance bars: async >= 1.5x sync (ISSUE 1, raw codec), staged >= 1.5x
async with zfp/q8 at >= 4 nodes x 8 clients (ISSUE 2), controller >=
1.3x static on the skewed chain with ZFP/LZ4 (ISSUE 3), and replicated
bottleneck measurably above the 1-replica plan with zero drops (ISSUE 4).

Procs scenario (``run_procs``, ISSUE 7): the elastic chain again, but
every replica is a SUPERVISED WORKER PROCESS (own OS process, loopback
sockets, byte framing) — then one stage-0 worker is SIGKILLed under
closed-loop load.  The bar is failure *semantics*, not speed: stranded
batches fail fast with NodeError (zero hangs, asserted — every future
resolves), the chain keeps serving on the survivor, and the supervisor
respawns the replica through the same epoch-fenced scale() a planned
resize uses, back to a numerically-correct full stage.  Results land in
BENCH_elastic_procs.json.

Every scenario accepts ``--transport`` (ISSUE 5): ``inproc`` (default),
``tcp`` (every chain hop over real loopback sockets with byte framing and
credit-window backpressure), or an emulated link such as
``link:10mbit,20ms`` reproducing the paper's CORE network conditions.
(``--procs`` always serves over the supervisor's own loopback sockets —
the processes make the transport.)

    PYTHONPATH=src python benchmarks/serve_load.py --nodes 4 --clients 8 \
        --codec zfp --min-staged-speedup 1.5
    PYTHONPATH=src python benchmarks/serve_load.py --rebalance \
        --codec zfp_lz4 --min-rebalance-speedup 1.3
    PYTHONPATH=src python benchmarks/serve_load.py --elastic --transport tcp
    PYTHONPATH=src:. python benchmarks/serve_load.py --procs
    PYTHONPATH=src python benchmarks/serve_load.py --smoke --transport tcp
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import threading
import time

# Each DEFER node models a SEPARATE edge device: give XLA one intra-op
# thread so per-node compute is serial and the chain's parallelism comes
# from the runtime (pipelining + batching), not from one GEMM grabbing
# every host core.  Must happen before jax initializes.
if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = ("--xla_cpu_multi_thread_eigen=false "
                               "intra_op_parallelism_threads=1")

import jax

# execute jitted computations on the calling (per-node) thread instead of
# funneling every node's apply through the CPU client's single dispatch
# stream — the chain's node parallelism is real, as on separate devices
jax.config.update("jax_cpu_enable_async_dispatch", False)

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.compile_cache import enable_compile_cache
from repro.core.graph import LayerGraph
from repro.runtime import ControllerConfig, InferenceEngine, TopologySpec
from repro.runtime.dispatcher import DispatcherCodecs
from repro.runtime.wire import WireCodec

D = 256
SEQ = 64
DEPTH = 16

CODECS = {
    "raw": WireCodec("raw", "none"),
    "zfp": WireCodec("zfp", "none", zfp_rate=16),
    "zfp_lz4": WireCodec("zfp", "lz4", zfp_rate=16),
    "q8": WireCodec("q8", "none"),
}


def serving_mlp(depth: int = DEPTH, d: int = D, seq: int = SEQ) -> LayerGraph:
    """A chain deep enough that a 4+ node partition has real per-stage
    compute (each hop is a [seq, d] x [d, d] GEMM, not a matvec), small
    enough that CPU jit stays in seconds."""
    g = LayerGraph("serve-mlp", jax.ShapeDtypeStruct((1, seq, d), np.float32))
    prev = ""
    for i in range(depth):
        g.layer(f"fc{i}",
                lambda p, x: jnp.tanh(x @ p["w"]),
                {"w": jax.ShapeDtypeStruct((d, d), np.float32)},
                (prev,),
                jax.ShapeDtypeStruct((1, seq, d), np.float32),
                flops=2.0 * seq * d * d)
        prev = f"fc{i}"
    return g


def skewed_chain(d: int = D, wide: int = 2 * D, narrow: int = D // 4,
                 seq: int = SEQ) -> LayerGraph:
    """A 16-layer encoder-style chain whose activation widths pinch and
    flare: three blocks of [d -> narrow -> wide -> wide -> d] plus a tail.
    The paper's ``equal_layers`` plan (cuts after layers 3 / 7 / 11) lands
    every inter-node hop on a WIDE activation, so the chain pays maximum
    codec + transfer per request; the cost-aware plan cuts at the narrow
    pinch points (after layers 1 / 5 / 9 — ``wide/narrow``x fewer bytes
    per hop) and hands the light tail node ~3x the layers of the head
    node.  The static planner cannot see this: its LinkModel knows wire
    bandwidth, not the measured per-byte codec cost that dominates a real
    chain — exactly what the serving controller calibrates online."""
    g = LayerGraph("skewed-chain",
                   jax.ShapeDtypeStruct((1, seq, narrow), np.float32))

    def fc(i: int, din: int, dout: int, prev: str) -> str:
        g.layer(f"fc{i}",
                lambda p, x: jnp.tanh(x @ p["w"]),
                {"w": jax.ShapeDtypeStruct((din, dout), np.float32)},
                (prev,),
                jax.ShapeDtypeStruct((1, seq, dout), np.float32),
                flops=2.0 * seq * din * dout)
        return f"fc{i}"

    dims = [narrow, d]                              # L0: narrow -> d
    for _ in range(3):                              # 3 pinch/flare blocks
        dims += [narrow, wide, wide, d]
    dims += [d, d, narrow]                          # tail, narrow output
    prev = ""
    for i, (din, dout) in enumerate(zip(dims, dims[1:])):
        prev = fc(i, din, dout, prev)
    return g


def sample(i: int, seq: int = SEQ, d: int = D) -> np.ndarray:
    rng = np.random.default_rng(i)
    return rng.normal(size=(1, seq, d)).astype(np.float32)


def build_engine(g: LayerGraph, params, topology, max_batch: int,
                 clients: int, codec: WireCodec, staged: bool,
                 **engine_kw) -> InferenceEngine:
    """``topology``: a TopologySpec, or an int for the classic 1-replica
    equal_layers chain (TopologySpec.chain sugar)."""
    eng = InferenceEngine(
        g, topology,
        DispatcherCodecs(data=codec, weights=WireCodec("raw", "none")),
        max_batch=max_batch, admission_depth=max(16, 4 * clients),
        staged=staged, **engine_kw)
    eng.configure(params)
    eng.precompile()
    eng.start()
    return eng


def warmup(eng: InferenceEngine, clients: int, seq: int, d: int,
           serialize: bool = False) -> None:
    """Run the same closed-loop pattern untimed so every batch-size jit
    specialization the load will hit is compiled before the clock starts."""
    for burst in (1, 2, clients):
        futs = [eng.submit(sample(10_000 + i, seq, d), client_id=i)
                for i in range(burst)]
        for f in futs:
            f.result()
    run_load(eng, clients, 4, seq, d, serialize=serialize)
    eng.dispatcher.drain()


def run_load(eng: InferenceEngine, clients: int, samples: int,
             seq: int, d: int, serialize: bool = False
             ) -> tuple[float, list]:
    """Closed-loop: each client thread awaits result i before sending i+1.
    ``serialize`` emulates the synchronous engine (one in flight, ever).
    Returns (wall_s, errors) — an empty error list certifies zero dropped
    or failed requests in the window."""
    lock = threading.Lock() if serialize else None
    barrier = threading.Barrier(clients + 1)
    errors: list = []

    def client(c: int) -> None:
        barrier.wait()
        try:
            for i in range(samples):
                x = sample(1000 * c + i, seq, d)
                if lock is not None:
                    with lock:
                        eng.submit(x, client_id=c).result()
                else:
                    eng.submit(x, client_id=c).result()
        except Exception as e:                  # pragma: no cover  # deferlint: swallow(recorded in errors[]; asserted after join)
            errors.append(e)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(clients)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    return time.perf_counter() - t0, errors


MODES = (
    # (mode, max_batch multiplier?, serialize clients, staged)
    ("sync", 1, True, False),
    ("async", 8, False, False),
    ("staged", 8, False, True),
)


def run(nodes: int = 4, clients: int = 8, samples: int = 16,
        codec: str = "zfp", repeats: int = 2, depth: int = DEPTH,
        d: int = D, seq: int = SEQ,
        transport: str = "inproc") -> list[dict]:
    g = serving_mlp(depth, d, seq)
    params = g.init(jax.random.PRNGKey(0))
    wire = CODECS[codec]
    spec = TopologySpec.chain(g, nodes, transport=transport)
    # the PR 1 modes run the PR 1 codec implementations; `staged` runs the
    # vectorized hot paths (both sides of the A/B are the code they claim)
    wire_pr1 = dataclasses.replace(wire, vectorized=False)
    rows = []
    for mode, max_batch, serialize, staged in MODES:
        eng = build_engine(g, params, spec, max_batch, clients,
                           wire if staged else wire_pr1, staged)
        warmup(eng, clients, seq, d, serialize=serialize)
        wall, rep, errs = _measure(eng, clients, samples, seq, d, repeats,
                                   serialize=serialize)
        eng.shutdown()
        assert not errs, errs
        rows.append({
            "mode": mode, "codec": rep.codec, "nodes": nodes,
            "transport": transport,
            "clients": clients, "samples": clients * samples,
            "wall_s": wall,
            "throughput_rps": rep.throughput_cps,
            "p50_ms": rep.p50_latency_s * 1e3,
            "p99_ms": rep.p99_latency_s * 1e3,
            "util_compute": float(np.mean([pn["util_compute"]
                                           for pn in rep.per_node])),
            "util_decode": float(np.mean([pn["util_decode"]
                                          for pn in rep.per_node])),
            "util_encode": float(np.mean([pn["util_encode"]
                                          for pn in rep.per_node])),
            "batch_mean": float(np.mean([pn["batch_mean"]
                                         for pn in rep.per_node])),
            "encodes_per_batch": float(np.mean([pn["encodes_per_batch"]
                                                for pn in rep.per_node])),
        })
    by_mode = {r["mode"]: r for r in rows}
    for r in rows:
        r["speedup_vs_sync"] = (r["throughput_rps"]
                                / by_mode["sync"]["throughput_rps"])
        r["speedup_vs_async"] = (r["throughput_rps"]
                                 / by_mode["async"]["throughput_rps"])
    return rows


# -- PR 3: controller vs static plan on a skewed chain -----------------------

def _measure(eng: InferenceEngine, clients: int, samples: int, seq: int,
             d: int, repeats: int,
             serialize: bool = False) -> tuple[float, "object", list]:
    """Best-of-N measured windows.  Scheduler jitter on an oversubscribed
    box only ever *adds* time, so min-wall is the lowest-noise estimator
    of a mode's real service rate."""
    best = None
    all_errs: list = []
    for _ in range(max(1, repeats)):
        eng.reset_window()
        wall, errs = run_load(eng, clients, samples, seq, d,
                              serialize=serialize)
        all_errs.extend(errs)
        rep = eng.report(samples=clients * samples, wall_s=wall)
        if best is None or wall < best[0]:
            best = (wall, rep)
    return best[0], best[1], all_errs


def _row(mode: str, wall: float, rep, nodes: int, clients: int,
         samples: int) -> dict:
    return {
        "mode": mode, "codec": rep.codec, "nodes": nodes,
        "clients": clients, "samples": clients * samples, "wall_s": wall,
        "throughput_rps": rep.throughput_cps,
        "p50_ms": rep.p50_latency_s * 1e3,
        "p99_ms": rep.p99_latency_s * 1e3,
        "epoch": rep.epoch, "cuts": "/".join(map(str, rep.cuts)),
        "batch_mean": float(np.mean([pn["batch_mean"]
                                     for pn in rep.per_node])),
        "util_compute_raw_max": max(pn["util_compute_raw"]
                                    for pn in rep.per_node),
        "coalesce_ms_mean": float(np.mean([pn["coalesce_s"]
                                           for pn in rep.per_node])) * 1e3,
        "max_batch_mean": float(np.mean([pn["max_batch"]
                                         for pn in rep.per_node])),
    }


def run_rebalance(nodes: int = 4, clients: int = 8, samples: int = 16,
                  codec: str = "zfp_lz4", repeats: int = 2,
                  d: int = D, wide: int = 2 * D, narrow: int = D // 4,
                  seq: int = SEQ, converge_s: float = 90.0,
                  smoke: bool = False, transport: str = "inproc") -> dict:
    """Static equal_layers vs controller-enabled serving on the skewed
    chain.  Both start from the SAME (bad) plan; only the controller may
    calibrate, migrate, and retune knobs.  Returns the full result dict
    (also written to BENCH_rebalance.json by main)."""
    g = skewed_chain(d, wide, narrow, seq)
    params = g.init(jax.random.PRNGKey(0))
    wire = CODECS[codec]
    rows = []

    # the paper's 1-replica equal_layers chain — the deliberately bad
    # static plan — on the selected transport backend
    spec = TopologySpec.chain(g, nodes, transport=transport)
    eng = build_engine(g, params, spec, 8, clients, wire, True)
    static_cuts = tuple(eng.dispatcher.partition.cuts)
    warmup(eng, clients, seq, narrow)
    wall, rep, errs = _measure(eng, clients, samples, seq, narrow, repeats)
    eng.shutdown()
    assert not errs, errs
    rows.append(_row("static", wall, rep, nodes, clients, samples))

    cfg = ControllerConfig(interval_s=0.25, min_requests=2 * clients,
                           cooldown_s=1.0, hysteresis=0.25,
                           ewma_alpha=0.5)
    eng = build_engine(g, params, spec, 8, clients, wire, True,
                       max_batch_cap=32, controller=cfg)
    warmup(eng, clients, seq, narrow)
    # convergence phase: serve until the controller commits a migration
    # (epoch > 0) — the untimed analogue of a warmed-up production chain
    conv_errs: list = []
    t0 = time.perf_counter()
    while (eng.dispatcher.epoch == 0
           and time.perf_counter() - t0 < converge_s):
        _, errs = run_load(eng, clients, 2, seq, narrow)
        conv_errs.extend(errs)
    converged_in = time.perf_counter() - t0
    if smoke and eng.dispatcher.epoch == 0:
        # the tiny raw-codec config may legitimately hold (costs nearly
        # balanced); the smoke gate still must exercise the live-migration
        # plumbing, so force a one-layer fence through the running chain
        eng.dispatcher.reconfigure(
            tuple(c + 1 for c in eng.dispatcher.partition.cuts))
    wall, rep, errs = _measure(eng, clients, samples, seq, narrow, repeats)
    reconfigs = list(eng.dispatcher.reconfig_records)
    eng.shutdown()
    assert not errs and not conv_errs, (errs, conv_errs)
    rows.append(_row("controller", wall, rep, nodes, clients, samples))

    speedup = (rows[1]["throughput_rps"] / rows[0]["throughput_rps"]
               if rows[0]["throughput_rps"] > 0 else 0.0)
    rows[1]["speedup_vs_static"] = speedup
    rows[0]["speedup_vs_static"] = 1.0
    emit("serve_rebalance", rows)
    return {
        "config": {"nodes": nodes, "clients": clients,
                   "samples_per_client": samples, "codec": codec,
                   "transport": transport,
                   "model": f"skewed-chain d={d} wide={wide} "
                            f"narrow={narrow} seq={seq} depth=16",
                   "static_cuts": static_cuts,
                   "protocol": "both modes best-of-N measured windows; "
                               "controller measured AFTER convergence "
                               "(epoch > 0 or timeout)"},
        "rows": rows,
        "speedup": speedup,
        "migrations": reconfigs,
        "converge_s": converged_in,
        "zero_dropped": True,        # asserted: no client saw an error
        "smoke": smoke,
        "notes": [
            "Both modes precompile and warm up identically and start from "
            "the same equal_layers plan; only the controller mode runs the "
            "feedback loop (cost calibration -> calibrated DP -> epoch-"
            "fenced migration + adaptive max_batch/coalesce_s).",
            "equal_layers cuts after layers 3/7/11 — all WIDE activations "
            "— so every hop pays maximum codec; the calibrated plan cuts "
            "the narrow pinch points after layers 1/5/9 (wide/narrow x "
            "fewer bytes per hop) and gives the tail node 3x the head "
            "node's layer count.",
            "The static planner cannot find the thin cuts: its LinkModel "
            "prices wire bandwidth, not the measured per-byte codec cost "
            "that dominates the chain — the controller calibrates that "
            "rate online from BatchTrace telemetry.",
            "zero_dropped is asserted, not sampled: every closed-loop "
            "client result is awaited through the migration and any "
            "failed/unresolved future fails the run.",
        ],
    }


# -- ISSUE 4: elastic membership on the bottleneck stage ----------------------

def _pound_while(eng, clients: int, seq: int, d: int, action,
                 settle_s: float = 0.2) -> tuple[dict, list, int]:
    """Closed-loop background load; run ``action()`` mid-flight; stop.
    Returns (action result, errors, requests completed) — the errors list
    must stay empty for the zero-dropped claim."""
    errors: list = []
    done = [0] * clients
    stop = threading.Event()

    def pound(c: int) -> None:
        i = 0
        try:
            while not stop.is_set():
                eng.submit(sample(777_000 + 1000 * c + i, seq, d),
                           client_id=("bg", c)).result(timeout=120)
                done[c] += 1
                i += 1
        except Exception as e:                  # pragma: no cover  # deferlint: swallow(recorded in errors[]; asserted after join)
            errors.append(e)

    threads = [threading.Thread(target=pound, args=(c,))
               for c in range(clients)]
    for t in threads:
        t.start()
    time.sleep(settle_s)                        # real in-flight traffic
    rec = action()
    time.sleep(settle_s)                        # post-fence traffic too
    stop.set()
    for t in threads:
        t.join()
    return rec, errors, sum(done)


def elastic_chain(narrow: int = 64, wide: int = 1024, seq: int = SEQ,
                  tail: int = 3) -> LayerGraph:
    """A chain built to have an UNSPLITTABLE codec-bound bottleneck: fc0
    widens narrow -> wide (stage 0, one layer, so no thinner cut exists),
    the first hop carries the wide activation (stage 0 must encode it),
    and the tail immediately narrows back so every other hop is cheap."""
    g = LayerGraph("elastic-chain",
                   jax.ShapeDtypeStruct((1, seq, narrow), np.float32))
    dims = [narrow, wide] + [narrow] * tail
    prev = ""
    for i, (din, dout) in enumerate(zip(dims, dims[1:])):
        g.layer(f"fc{i}",
                lambda p, x: jnp.tanh(x @ p["w"]),
                {"w": jax.ShapeDtypeStruct((din, dout), np.float32)},
                (prev,),
                jax.ShapeDtypeStruct((1, seq, dout), np.float32),
                flops=2.0 * seq * din * dout)
        prev = f"fc{i}"
    return g


def run_elastic(clients: int = 24, samples: int = 8,
                codec: str = "zfp_lz4", repeats: int = 2,
                narrow: int = 64, wide: int = 1024, seq: int = SEQ,
                max_replicas: int = 3, transport: str = "inproc") -> dict:
    """1 -> N replicas on the bottleneck stage, scaled under load.

    Stage 0 is one widening layer whose egress ENCODES the wide
    activation: with ZFP/LZ4 that encode saturates the stage (~0.98 busy
    measured) while the receiving decode is ~6x cheaper, and the cut
    cannot move (a single layer is already minimal) — exactly the
    situation where the controller's replica dimension (and this
    scenario's explicit ``scale()``) is the remaining lever.  The numpy
    codec is single-threaded per replica, so replicas genuinely
    parallelize the wire encode.

    Closed-loop clients must OVERSUBSCRIBE the 1-replica capacity
    (default 24): replication raises a stage's service *rate*, never a
    request's own latency, so an unsaturated closed loop would measure no
    change by construction."""
    g = elastic_chain(narrow, wide, seq)
    d = narrow
    params = g.init(jax.random.PRNGKey(0))
    wire = CODECS[codec]
    spec = TopologySpec.chain(g, 2, cuts=(1,), transport=transport)
    eng = build_engine(g, params, spec, 8, clients, wire, True)
    bottleneck = 0                              # the wide-encoding stage
    warmup(eng, clients, seq, d)

    rows = []
    scale_recs = []

    def measure(label: str) -> None:
        wall, rep, errs = _measure(eng, clients, samples, seq, d, repeats)
        assert not errs, errs
        row = _row(label, wall, rep, sum(rep.replicas), clients, samples)
        row["replicas"] = "x".join(map(str, rep.replicas))
        rows.append(row)

    # membership ladder 1 -> 2 -> .. -> N -> 1: measuring the 1-replica
    # plan at BOTH ends and taking its best window makes the baseline
    # symmetric to box drift over the minutes the run takes, and the
    # final step exercises DRAIN under load in the recorded benchmark
    ladder = list(range(2, max_replicas + 1)) + [1]
    measure("replicas=1")
    for n in ladder:
        # the scale itself happens UNDER closed-loop load: the epoch
        # fence must lose nothing while membership changes.  precompile
        # traces the spawned replicas' batch shapes BEFORE they join the
        # routing set — a cold replica would otherwise serve its first
        # waves through XLA compiles and read as slower than no replica
        rec, errs, completed = _pound_while(
            eng, clients, seq, d,
            lambda n=n: eng.scale(bottleneck, n, precompile=True))
        # zero-drop is ASSERTED, not sampled: any client error during a
        # live scale aborts the benchmark instead of being counted
        assert not errs, errs
        rec["requests_during_scale"] = completed
        scale_recs.append(rec)
        measure(f"replicas={n}" + ("-drained" if n == 1 else ""))
    eng.shutdown()

    base = max(r["throughput_rps"] for r in rows
               if r["mode"].startswith("replicas=1"))
    for r in rows:
        r["speedup_vs_1_replica"] = (r["throughput_rps"] / base
                                     if base > 0 else 0.0)
    best = max((r for r in rows if not r["mode"].startswith("replicas=1")),
               key=lambda r: r["throughput_rps"])
    emit("serve_elastic", rows)
    return {
        "config": {"clients": clients, "samples_per_client": samples,
                   "codec": codec, "transport": transport,
                   "model": f"elastic-chain narrow={narrow} wide={wide} "
                            f"seq={seq}",
                   "topology": f"2 stages, cut after layer 1 (stage 0 = "
                               f"the single widening layer encoding the "
                               f"{wide}-wide hop), scale stage "
                               f"{bottleneck} 1->{max_replicas}",
                   "protocol": "membership ladder 1->2->..->N->1, each "
                               "scale() executed under closed-loop load "
                               "(zero-drop asserted on every in-flight "
                               "future), best-of-N measured windows per "
                               "membership; baseline = best 1-replica "
                               "window from either end of the ladder "
                               "(drift-symmetric)"},
        "rows": rows,
        "scales": scale_recs,
        "speedup": best["speedup_vs_1_replica"],
        "best_replicas": best["replicas"],
        "zero_dropped": True,   # asserted: any drop aborts the run above
        "notes": [
            "Stage 0 is a single layer, so no cut migration can shrink "
            "it: the wide-hop encode it pays is irreducible by the DP, "
            "which isolates the replica dimension.",
            "Each scale() rides the epoch fence: spawned replicas are "
            "configured over the wire with the stage's full weights and "
            "fenced into the routing set; every request in flight during "
            "the fence resolves (asserted, not sampled).",
            "Host ceiling: this container has 2 cores and one XLA apply "
            "already spends ~1.3 of them (two concurrent jitted GEMM "
            "loops aggregate only ~1.33x one loop, measured), so "
            "compute-bound stages cannot demonstrate replication "
            "in-process; the codec-bound stage can because the numpy "
            "codec is strictly single-threaded per replica.  LZ4's "
            "Python-level match loops still serialize part of each "
            "encode under the GIL, which is why 2-3 replicas land at "
            "~1.2-1.5x rather than 2-3x; on separate devices (the "
            "paper's setting) the same fence/routing machinery scales "
            "with the hardware.",
        ],
    }


# -- ISSUE 7: process-per-replica serving + self-healing drill ----------------

def run_procs(clients: int = 8, samples: int = 8, codec: str = "raw",
              repeats: int = 2, narrow: int = 16, wide: int = 64,
              seq: int = 16, replay: bool = False) -> dict:
    """Serve the elastic chain with every replica in its OWN OS process
    (supervised workers over loopback sockets), then SIGKILL a stage-0
    worker under closed-loop load and measure across the self-heal.

    ``replay=False`` (ISSUE 7 contract): the stranded batches fail fast
    (NodeError, never a hang), the chain keeps answering on the
    survivor, and the supervisor respawns the replica through the same
    epoch-fenced scale() a planned resize uses.

    ``replay=True`` (ISSUE 8 contract): a RetryPolicy is installed, so
    the dispatcher retains every request's encoded input and replays
    the stranded batches through the healed chain — the kill window
    must produce ZERO client-visible failures (asserted: the error list
    stays empty), and the record gains replay-rate and added-latency
    columns (kill-window p50 vs the undisturbed baseline p50).

    Either way zero-hang is asserted (every future resolves) and the
    healed chain must reproduce reference numerics."""
    from repro.runtime import NodeError, RetryPolicy
    from repro.runtime.supervisor import SupervisorConfig, supervised_engine
    from tools.chaos import Chaos
    g = elastic_chain(narrow, wide, seq)
    d = narrow
    params = g.init(jax.random.PRNGKey(0))
    wire = CODECS[codec]
    topo = TopologySpec.chain(g, 2, cuts=(1,)).with_replicas(0, 2)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # workers rebuild the graph from THIS file (code pre-installed on
    # every node, the paper's model); they import repro + benchmarks, so
    # their PYTHONPATH needs the repo root alongside src
    pyp = [root, os.path.join(root, "src")]
    if os.environ.get("PYTHONPATH"):
        pyp.append(os.environ["PYTHONPATH"])
    cfg = SupervisorConfig(
        graph_factory=os.path.abspath(__file__) + ":elastic_chain",
        graph_args={"narrow": narrow, "wide": wide, "seq": seq},
        heartbeat_s=0.2, backoff_initial_s=0.2, backoff_max_s=1.0,
        env={"PYTHONPATH": os.pathsep.join(pyp)})
    policy = RetryPolicy(max_attempts=5, backoff_s=0.05,
                         retry_budget=64.0, refill_per_s=32.0) \
        if replay else None
    eng, sup = supervised_engine(
        g, params, topo, cfg,
        codecs=DispatcherCodecs(data=wire, weights=WireCodec("raw", "none")),
        max_batch=8, admission_depth=max(16, 4 * clients),
        retry_policy=policy)
    chaos = Chaos(sup)
    rows = []
    try:
        eng.start()
        warmup(eng, clients, seq, d)

        def measure(label: str) -> None:
            wall, rep, errs = _measure(eng, clients, samples, seq, d,
                                       repeats)
            assert not errs, errs
            row = _row(label, wall, rep, sum(rep.replicas), clients,
                       samples)
            row["replicas"] = "x".join(map(str, rep.replicas))
            rows.append(row)

        measure("procs=2x1")
        # the drill: SIGKILL one stage-0 worker while closed-loop load is
        # in flight.  Replay OFF: NodeError on the stranded batches is
        # the contract (fail fast, never a hang).  Replay ON: the
        # dispatcher re-admits the retained inputs through the healed
        # stage, so the contract tightens to ZERO client-visible
        # failures.  Either way a hang or a foreign exception aborts.
        def kill() -> dict:
            pid = chaos.kill(chaos.pick(stage=0))
            chaos.wait_death(stage=0, timeout=30)
            return {"killed_pid": pid}

        eng.reset_window()              # isolate the kill-window latency
        rec, errors, completed = _pound_while(eng, clients, seq, d, kill)
        kill_rep = eng.report()
        if replay:
            assert not errors, errors   # exactly-once: no failure leaks
            failed = 0
        else:
            hard = [e for e in errors if not isinstance(e, NodeError)]
            assert not hard, hard
            failed = len(errors) - len(hard)
        chaos.wait_respawn(stage=0, timeout=60)
        assert chaos.wait_stage_full(eng.dispatcher, 0, timeout=60) == 2
        rec["requests_during_kill"] = completed
        rec["failed_fast"] = failed
        if replay:
            st = eng.dispatcher.replay_stats
            rec["replays"] = st.replays
            rec["replay_rate"] = st.replays / max(1, completed)
            rec["kill_window_p50_ms"] = kill_rep.p50_latency_s * 1e3
            rec["baseline_p50_ms"] = rows[0]["p50_ms"]
            rec["added_latency_p50_ms"] = (rec["kill_window_p50_ms"]
                                           - rec["baseline_p50_ms"])
        measure("healed=2x1")
        # reference numerics through the healed (respawned) chain
        x = sample(424_242, seq, d)
        np.testing.assert_allclose(
            eng.submit(x).result(timeout=120),
            np.asarray(g.apply(params, x)), atol=1e-4)
    finally:
        eng.shutdown()
        sup.close()
    kinds = [e["kind"] for e in sup.events]
    assert kinds.count("death") == 1 and kinds.count("respawn") >= 1, kinds
    base = rows[0]["throughput_rps"]
    for r in rows:
        r["vs_baseline"] = r["throughput_rps"] / base if base > 0 else 0.0
    emit("serve_procs", rows)
    notes = [
        "Workers rebuild the layer graph locally from the factory "
        "spec (code is pre-installed on every device, as in the "
        "paper); only topology and weights travel, as NodePlan "
        "framing over the control socket.",
    ]
    if replay:
        notes.append(
            "Replay ON: the dispatcher retained every request's encoded "
            "input, classified the kill's stranded batches as "
            "infrastructure failures, and re-admitted them under an "
            "incremented attempt tag — zero client-visible failures is "
            "asserted, not sampled.  added_latency_p50_ms is the price "
            "of exactly-once during the kill window (detection + "
            "backoff + re-serve) vs the undisturbed baseline.")
    else:
        notes.append(
            "The kill window's failures are exactly the batches inside "
            "the dead worker's pipeline (failed_fast above) — at-most-"
            "once on a crash, never a hang; survivors keep serving "
            "through the heal and the respawn rides the standard epoch-"
            "fenced scale() path.")
    return {
        "config": {"clients": clients, "samples_per_client": samples,
                   "codec": codec, "replay": replay,
                   "model": f"elastic-chain narrow={narrow} wide={wide} "
                            f"seq={seq}",
                   "topology": "2 stages, stage 0 x2 replicas, every "
                               "replica a supervised worker process "
                               "(loopback sockets, byte framing)",
                   "protocol": "measure 2-proc baseline; SIGKILL one "
                               "stage-0 worker under closed-loop load "
                               + ("(retained inputs replay through the "
                                  "healed stage: zero client-visible "
                                  "failures asserted)" if replay else
                                  "(stranded batches must fail fast, "
                                  "nothing may hang)")
                               + "; wait for the supervisor's respawn; "
                                 "measure healed"},
        "rows": rows,
        "kill": rec,
        "events": [e for e in sup.events
                   if e["kind"] in ("death", "respawn", "degraded")],
        "zero_hangs": True,     # asserted: every future resolved
        "notes": notes,
    }


def run_decode(sessions: int = 8, rounds: int = 2, new_tokens: int = 32,
               codec: str = "raw", transport: str = "inproc",
               smoke: bool = False) -> dict:
    """Autoregressive decode serving (ISSUE 9): N concurrent sessions
    greedy-decode closed-loop through a 2-stage chain with per-stage
    resident KV caches.  Reports tokens/s, per-step latency, and the
    decode contract's whole point — the per-step cross-hop payload
    (O(d_model), the newest token only) against what resending the full
    sequence through the same codec would cost every step."""
    from repro.models.lm_graph import (decode_lm_graph,
                                       pipeline_decode_reference)
    if smoke:
        cfg = dict(vocab=32, d_model=16, n_layers=2, num_heads=2,
                   kv_heads=2, head_dim=8, d_ff=32)
    else:
        cfg = dict(vocab=256, d_model=128, n_layers=4, num_heads=4,
                   kv_heads=4, head_dim=32, d_ff=256)
    prompt_len = 8
    cfg["cache_len"] = prompt_len + new_tokens + 2
    g = decode_lm_graph(**cfg)
    params = g.init(jax.random.PRNGKey(0))
    # lossless data path (greedy decode must be bit-identical across
    # hops) with the small-frame bypass sized to catch every token step
    wire = dataclasses.replace(CODECS[codec], small_bypass=4096)
    topo = TopologySpec.chain(g, 2, transport=transport)
    eng = InferenceEngine(
        g, topo, DispatcherCodecs(data=wire, weights=WireCodec("raw", "none")),
        max_batch=max(4, sessions), admission_depth=max(16, 4 * sessions))
    eng.configure(params)
    eng.start()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg["vocab"], size=prompt_len).tolist()
               for _ in range(sessions)]
    try:
        # warm every jit specialization the load will hit (prefill at the
        # prompt shape, the batched step at 1..pow2(sessions) rows)
        warm = [eng.generate(p, 3) for p in prompts]
        for gen in warm:
            next(gen)
        for gen in warm:
            list(gen)

        step_ms: list[float] = []
        lock = threading.Lock()

        def one_client(i: int) -> None:
            for _ in range(rounds):
                gen = eng.generate(prompts[i], new_tokens)
                next(gen)                   # prefill
                while True:
                    t0 = time.perf_counter()
                    try:
                        next(gen)
                    except StopIteration:
                        break
                    with lock:
                        step_ms.append((time.perf_counter() - t0) * 1e3)

        threads = [threading.Thread(target=one_client, args=(i,))
                   for i in range(sessions)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        toks = sessions * rounds * new_tokens

        # the payload contract, measured on the stage-0 hop: steps only
        # (open and close bracketed out), against a full-sequence resend
        # of the final-prefix boundary activations through the SAME codec
        gen = eng.generate(prompts[0], new_tokens)
        next(gen)
        node = eng.dispatcher.stages[0].live_replicas()[0]
        node.reset_stats()
        toks_meas = [next(gen) for _ in range(new_tokens - 1)]
        per_step = node.snapshot()["payload_bytes"] / (new_tokens - 1)
        gen.close()
        full = np.zeros((1, prompt_len + new_tokens, cfg["d_model"]),
                        np.float32)
        full_bytes = len(wire.encode_array(full))
        ref = pipeline_decode_reference(g, params, prompts[0], new_tokens)
        assert toks_meas == ref[1:], \
            "decode diverged from the single-device reference"
    finally:
        eng.shutdown()
    return {
        "sessions": sessions, "rounds": rounds, "new_tokens": new_tokens,
        "prompt_len": prompt_len, "model": cfg, "codec": wire.label,
        "transport": transport, "wall_s": wall,
        "tokens_per_s": toks / wall,
        "step_p50_ms": float(np.percentile(step_ms, 50)),
        "step_p99_ms": float(np.percentile(step_ms, 99)),
        "per_step_hop_bytes": per_step,
        "full_resend_hop_bytes": full_bytes,
        "hop_savings_x": full_bytes / per_step,
        "reference_bit_identical": True,    # asserted above
    }


def _bench_suffix(transport: str, procs: bool = False) -> str:
    """Per-scenario BENCH file suffix: 'inproc' keeps the bare name, any
    other binding (including distinct link shapes) records side by side
    — link:10mbit,20ms -> '_link_10mbit_20ms' — and process-backed runs
    append '_procs' so in-process and multi-process results coexist."""
    s = ""
    if transport != "inproc":
        s = "_" + re.sub(r"[^A-Za-z0-9]+", "_", transport).strip("_")
    if procs:
        s += "_procs"
    return s


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--clients", type=int, default=None,
                    help="closed-loop clients (default 8; 24 for "
                         "--elastic, which must oversubscribe the "
                         "1-replica capacity to see a rate change)")
    ap.add_argument("--samples", type=int, default=None,
                    help="samples per client (default 16; 8 for "
                         "--elastic)")
    ap.add_argument("--codec", choices=sorted(CODECS), default=None,
                    help="wire codec (default zfp; zfp_lz4 for --elastic, "
                         "whose bottleneck is the asymmetric wide-hop "
                         "encode)")
    ap.add_argument("--repeats", type=int, default=2,
                    help="measured windows per mode; fastest is reported")
    ap.add_argument("--transport", default="inproc",
                    help="channel backend for every stage: inproc "
                         "(default), tcp (real loopback sockets), or an "
                         "emulated link like link:10mbit,20ms — the "
                         "paper's CORE network conditions (ISSUE 5)")
    ap.add_argument("--min-speedup", type=float, default=0.0,
                    help="exit nonzero if async/sync < this (ISSUE 1 bar)")
    ap.add_argument("--min-staged-speedup", type=float, default=0.0,
                    help="exit nonzero if staged/async < this (ISSUE 2 bar)")
    ap.add_argument("--rebalance", action="store_true",
                    help="run the PR 3 skewed-chain controller scenario")
    ap.add_argument("--min-rebalance-speedup", type=float, default=0.0,
                    help="exit nonzero if controller/static < this "
                         "(ISSUE 3 bar)")
    ap.add_argument("--elastic", action="store_true",
                    help="run the ISSUE 4 replica-elasticity scenario "
                         "(scale the bottleneck stage 1->3 under load)")
    ap.add_argument("--min-elastic-speedup", type=float, default=0.0,
                    help="exit nonzero if best-replicated/1-replica < "
                         "this (ISSUE 4 bar)")
    ap.add_argument("--procs", action="store_true",
                    help="run the ISSUE 7 process-per-replica scenario: "
                         "supervised worker processes, SIGKILL one under "
                         "load, measure across the self-heal")
    ap.add_argument("--replay", action="store_true",
                    help="with --procs: install a RetryPolicy so the "
                         "SIGKILL drill must be invisible to clients "
                         "(ISSUE 8 exactly-once semantics: stranded "
                         "batches replay through the healed stage); "
                         "records BENCH_elastic_replay.json")
    ap.add_argument("--decode", action="store_true",
                    help="run the ISSUE 9 autoregressive decode scenario: "
                         "concurrent sessions generating closed-loop "
                         "through a 2-stage chain with resident KV "
                         "caches; records tokens/s and per-step hop "
                         "bytes vs a full-sequence resend")
    ap.add_argument("--sessions", type=int, default=None,
                    help="with --decode: concurrent decode sessions "
                         "(default 8; 2 with --smoke)")
    ap.add_argument("--new-tokens", type=int, default=None,
                    help="with --decode: tokens generated per session "
                         "per round (default 32)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny raw-codec config (seconds): plumbing gate "
                         "for CI, including one live reconfiguration")
    args = ap.parse_args()

    if args.decode:
        smoke = args.smoke
        res = run_decode(sessions=args.sessions or (2 if smoke else 8),
                         rounds=1 if smoke else args.repeats,
                         new_tokens=args.new_tokens or 32,
                         codec=args.codec or "raw",
                         transport=args.transport, smoke=smoke)
        if smoke:
            # CI gate: tokens flowed, greedy output matched the
            # single-device reference (asserted inside run_decode), and
            # the per-step hop payload beat a full-sequence resend 10x
            assert res["hop_savings_x"] >= 10.0, res
            print(f"decode smoke ok ({args.transport}): "
                  f"{res['tokens_per_s']:.1f} tok/s across "
                  f"{res['sessions']} sessions, per-step hop "
                  f"{res['per_step_hop_bytes']:.0f} B vs full resend "
                  f"{res['full_resend_hop_bytes']} B "
                  f"({res['hop_savings_x']:.1f}x), reference "
                  "bit-identity asserted")
            return
        res = {"benchmark": "benchmarks/serve_load.py --decode",
               "date": time.strftime("%Y-%m-%d"),
               "host": f"{os.cpu_count()}-core CPU container, "
                       f"jax {jax.__version__} cpu, XLA intra_op=1, "
                       "cpu async dispatch off",
               "acceptance": {
                   "bar": "concurrent sessions decode through the chain "
                          "with resident KV caches: per-step cross-hop "
                          "payload >= 10x smaller than a full-sequence "
                          "resend, greedy output bit-identical to the "
                          "single-device reference",
                   "result": f"{'PASS' if res['hop_savings_x'] >= 10 else 'FAIL'}"
                             f" at {res['hop_savings_x']:.1f}x hop "
                             f"savings, {res['tokens_per_s']:.1f} tok/s, "
                             "bit-identity asserted",
               },
               **res}
        with open(f"BENCH_decode{_bench_suffix(args.transport)}.json",
                  "w") as f:
            json.dump(res, f, indent=2, default=str)
        print(f"decode: {res['tokens_per_s']:.1f} tok/s "
              f"({res['sessions']} sessions x {res['rounds']} rounds x "
              f"{res['new_tokens']} tokens, {res['codec']}, "
              f"{res['transport']})")
        print(f"  step p50 {res['step_p50_ms']:.1f} ms  "
              f"p99 {res['step_p99_ms']:.1f} ms")
        print(f"  per-step hop {res['per_step_hop_bytes']:.0f} B vs "
              f"full-sequence resend {res['full_resend_hop_bytes']} B "
              f"= {res['hop_savings_x']:.1f}x smaller")
        return

    if args.smoke and args.procs:
        # tiny process-mode gate (seconds): two worker processes on
        # stage 0, SIGKILL one under closed-loop load.  With --replay
        # the kill must be INVISIBLE to clients (zero failures, the CI
        # replay leg); without, the stranded batches must fail fast.
        res = run_procs(clients=2, samples=2, codec="raw", repeats=1,
                        replay=args.replay)
        k = res["kill"]
        extra = (f", {k['replays']} replay(s)" if args.replay
                 else f", {k['failed_fast']} failed fast")
        print(f"procs smoke ok ({'replay' if args.replay else 'fail-fast'}):"
              f" killed pid {k['killed_pid']}, "
              f"{k['requests_during_kill']} requests in the kill window"
              + extra + ", healed to full stage (asserted)")
        return

    if args.smoke:
        # small model, 2 nodes, raw codec: exercises admission, staging,
        # batch wire framing, the controller step, and a live repartition
        # (--transport tcp runs the whole gate over real loopback sockets)
        rows = run(nodes=2, clients=2, samples=3, codec="raw", repeats=1,
                   depth=6, d=64, seq=16, transport=args.transport)
        emit("serve_load_smoke", rows)
        res = run_rebalance(nodes=2, clients=2, samples=3, codec="raw",
                            repeats=1, d=64, wide=128, narrow=16, seq=16,
                            converge_s=10.0, smoke=True,
                            transport=args.transport)
        assert res["zero_dropped"]
        # a live repartition MUST have happened (controller-decided or the
        # forced smoke fence) and lost nothing — this is the plumbing the
        # CI gate exists to catch
        assert res["rows"][1]["epoch"] >= 1, res["rows"][1]
        # the elastic plumbing too: spawn + drain a replica under load
        # (tiny config, seconds) with zero dropped requests
        eres = run_elastic(clients=2, samples=3, codec="raw", repeats=1,
                           narrow=16, wide=64, seq=16, max_replicas=2,
                           transport=args.transport)
        assert eres["zero_dropped"], eres
        # the ladder went 1 -> 2 -> 1: a spawn AND a drain both fenced
        # through a loaded chain
        assert any(r["replicas"] == "2x1" for r in eres["rows"]), eres
        assert eres["rows"][-1]["replicas"] == "1x1", eres["rows"][-1]
        assert eres["rows"][-1]["epoch"] == 2, eres["rows"][-1]
        print(f"smoke ok ({args.transport}): "
              f"staged {rows[-1]['throughput_rps']:.1f} req/s, "
              f"rebalance epoch {res['rows'][1]['epoch']}, "
              f"controller {res['rows'][1]['throughput_rps']:.1f} req/s, "
              f"elastic {eres['rows'][0]['throughput_rps']:.1f} -> "
              f"{eres['rows'][-1]['throughput_rps']:.1f} req/s")
        return

    if args.elastic:
        res = run_elastic(args.clients or 24, args.samples or 8,
                          args.codec or "zfp_lz4", args.repeats,
                          transport=args.transport)
        res = {"benchmark": "benchmarks/serve_load.py --elastic",
               "date": time.strftime("%Y-%m-%d"),
               "host": f"{os.cpu_count()}-core CPU container, "
                       f"jax {jax.__version__} cpu, XLA intra_op=1, "
                       "cpu async dispatch off",
               "acceptance": {
                   "bar": "a replicated bottleneck stage yields measurably "
                          "higher throughput than the 1-replica plan, with "
                          "zero requests dropped during the live scale()s",
                   "result": f"{'PASS' if res['speedup'] > 1.0 and res['zero_dropped'] else 'FAIL'}"
                             f" at {res['speedup']:.2f}x "
                             f"({res['best_replicas']} replicas), "
                             f"zero_dropped (asserted)",
               },
               **res}
        with open(f"BENCH_elastic{_bench_suffix(args.transport)}.json",
                  "w") as f:
            json.dump(res, f, indent=2, default=str)
        print(f"elastic speedup: {res['speedup']:.2f}x at "
              f"{res['best_replicas']} replicas (zero dropped: asserted)")
        for r in res["rows"]:
            print(f"  {r['mode']:<12} {r['throughput_rps']:6.1f} req/s  "
                  f"p50 {r['p50_ms']:6.1f} ms  "
                  f"({r['speedup_vs_1_replica']:.2f}x)")
        if args.min_elastic_speedup \
                and res["speedup"] < args.min_elastic_speedup:
            raise SystemExit(
                f"elastic speedup {res['speedup']:.2f}x < required "
                f"{args.min_elastic_speedup}x")
        return

    if args.procs:
        res = run_procs(args.clients or 8, args.samples or 8,
                        args.codec or "raw", args.repeats,
                        replay=args.replay)
        k = res["kill"]
        if args.replay:
            acceptance = {
                "bar": "with a RetryPolicy installed, a SIGKILLed worker "
                       "process is invisible to clients: zero failures, "
                       "zero hangs, stranded batches replayed through "
                       "the healed stage, reference numerics",
                "result": "PASS (asserted: zero client-visible failures; "
                          f"{k['replays']} replay(s), replay_rate "
                          f"{k['replay_rate']:.3f}, kill-window p50 "
                          f"{k['added_latency_p50_ms']:+.1f} ms vs "
                          "baseline)",
            }
            out = "BENCH_elastic_replay.json"
        else:
            acceptance = {
                "bar": "a SIGKILLed worker process fails its stranded "
                       "batches fast (NodeError, zero hangs), the "
                       "chain keeps serving on the survivor, and the "
                       "supervisor respawns the replica to a full, "
                       "numerically-correct stage",
                "result": "PASS (all asserted: fail-fast, respawn, "
                          f"stage full, reference numerics; "
                          f"{k['failed_fast']} batches "
                          "failed fast during the kill window)",
            }
            out = (f"BENCH_elastic"
                   f"{_bench_suffix(args.transport, procs=True)}.json")
        res = {"benchmark": "benchmarks/serve_load.py --procs"
                            + (" --replay" if args.replay else ""),
               "date": time.strftime("%Y-%m-%d"),
               "host": f"{os.cpu_count()}-core CPU container, "
                       f"jax {jax.__version__} cpu, XLA intra_op=1, "
                       "cpu async dispatch off",
               "acceptance": acceptance,
               **res}
        with open(out, "w") as f:
            json.dump(res, f, indent=2, default=str)
        if args.replay:
            print(f"procs+replay: killed pid {k['killed_pid']}, "
                  f"{k['requests_during_kill']} requests in the kill "
                  f"window, 0 client-visible failures (asserted), "
                  f"{k['replays']} replay(s) "
                  f"(rate {k['replay_rate']:.3f}), kill-window p50 "
                  f"{k['kill_window_p50_ms']:.1f} ms vs baseline "
                  f"{k['baseline_p50_ms']:.1f} ms "
                  f"({k['added_latency_p50_ms']:+.1f} ms)")
        else:
            print(f"procs: killed pid {k['killed_pid']}, "
                  f"{k['failed_fast']} failed fast of "
                  f"{k['requests_during_kill']} in the kill window, "
                  "healed to full stage (asserted)")
        for r in res["rows"]:
            print(f"  {r['mode']:<12} {r['throughput_rps']:6.1f} req/s  "
                  f"p50 {r['p50_ms']:6.1f} ms  "
                  f"({r['vs_baseline']:.2f}x vs baseline)")
        return

    if args.rebalance:
        res = run_rebalance(args.nodes, args.clients or 8,
                            args.samples or 16, args.codec or "zfp_lz4",
                            args.repeats, transport=args.transport)
        res = {"benchmark": "benchmarks/serve_load.py --rebalance",
               "date": time.strftime("%Y-%m-%d"),
               "host": f"{os.cpu_count()}-core CPU container, "
                       f"jax {jax.__version__} cpu, XLA intra_op=1, "
                       "cpu async dispatch off",
               "acceptance": {
                   "bar": "controller >= 1.3x static equal_layers on the "
                          "skewed chain (ZFP/LZ4, 4 nodes x 8 clients), "
                          "zero in-flight requests dropped by the hot "
                          "repartition",
                   "result": f"{'PASS' if res['speedup'] >= 1.3 else 'FAIL'}"
                             f" at {res['speedup']:.2f}x, zero_dropped="
                             f"{res['zero_dropped']}",
               },
               **res}
        with open(f"BENCH_rebalance{_bench_suffix(args.transport)}.json",
                  "w") as f:
            json.dump(res, f, indent=2, default=str)
        print(f"controller/static speedup: {res['speedup']:.2f}x "
              f"(epoch {res['rows'][1]['epoch']}, "
              f"cuts {res['rows'][0]['cuts']} -> {res['rows'][1]['cuts']}, "
              f"zero dropped: {res['zero_dropped']})")
        if args.min_rebalance_speedup \
                and res["speedup"] < args.min_rebalance_speedup:
            raise SystemExit(
                f"rebalance speedup {res['speedup']:.2f}x < required "
                f"{args.min_rebalance_speedup}x")
        return

    rows = run(args.nodes, args.clients or 8, args.samples or 16,
               args.codec or "zfp", args.repeats,
               transport=args.transport)
    emit("serve_load", rows)
    by_mode = {r["mode"]: r for r in rows}
    s_async = by_mode["async"]["speedup_vs_sync"]
    s_staged = by_mode["staged"]["speedup_vs_async"]
    print(f"async/sync speedup:   {s_async:.2f}x "
          f"({by_mode['async']['throughput_rps']:.1f} vs "
          f"{by_mode['sync']['throughput_rps']:.1f} req/s)")
    print(f"staged/async speedup: {s_staged:.2f}x "
          f"({by_mode['staged']['throughput_rps']:.1f} vs "
          f"{by_mode['async']['throughput_rps']:.1f} req/s, "
          f"codec {by_mode['staged']['codec']})")
    if args.min_speedup and s_async < args.min_speedup:
        raise SystemExit(
            f"async speedup {s_async:.2f}x < required {args.min_speedup}x")
    if args.min_staged_speedup and s_staged < args.min_staged_speedup:
        raise SystemExit(f"staged speedup {s_staged:.2f}x < "
                         f"required {args.min_staged_speedup}x")


if __name__ == "__main__":
    enable_compile_cache()
    main()
