"""Partitioner: DP optimality (vs brute force, hypothesis), strategies,
cut costs, and graph slicing/reassembly."""
import itertools

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.graph import LayerGraph
from repro.core.partitioner import (CalibratedCosts, ComputeModel, LinkModel,
                                    _linear_partition_dp, bounds_bottleneck,
                                    calibrated_partition, partition)


def chain_graph(flops, out_elems=None):
    g = LayerGraph("toy", jax.ShapeDtypeStruct((4,), np.float32))
    prev = ""
    out_elems = out_elems or [4] * len(flops)
    for i, (f, oe) in enumerate(zip(flops, out_elems)):
        g.layer(f"l{i}", lambda p, x: x, {}, (prev,),
                jax.ShapeDtypeStruct((oe,), np.float32), flops=f)
        prev = f"l{i}"
    return g


def brute_force_bottleneck(w, edge, k):
    n = len(w)
    best = float("inf")
    for cuts in itertools.combinations(range(1, n), k - 1):
        bounds = [0, *cuts, n]
        cost = max(sum(w[lo:hi]) + edge[hi - 1]
                   for lo, hi in zip(bounds, bounds[1:]))
        best = min(best, cost)
    return best


@settings(max_examples=50, deadline=None)
@given(st.lists(st.floats(0.1, 100.0), min_size=3, max_size=9),
       st.integers(2, 4))
def test_dp_optimal_vs_brute_force(w, k):
    if k > len(w):
        k = len(w)
    edge = [0.0] * len(w)
    bounds = _linear_partition_dp(np.array(w), np.array(edge), k)
    got = max(sum(w[lo:hi]) for lo, hi in zip(bounds, bounds[1:]))
    assert got <= brute_force_bottleneck(w, edge, k) + 1e-9


@settings(max_examples=30, deadline=None)
@given(st.lists(st.tuples(st.floats(0.1, 50.0), st.floats(0.0, 10.0)),
                min_size=3, max_size=8),
       st.integers(2, 3))
def test_dp_optimal_with_edge_costs(pairs, k):
    w = [p[0] for p in pairs]
    edge = [p[1] for p in pairs]
    edge[-1] = 0.0
    if k > len(w):
        k = len(w)
    bounds = _linear_partition_dp(np.array(w), np.array(edge), k)
    got = max(sum(w[lo:hi]) + edge[hi - 1]
              for lo, hi in zip(bounds, bounds[1:]))
    assert got <= brute_force_bottleneck(w, edge, k) + 1e-9


def test_partition_properties():
    g = chain_graph([1e6 * (i + 1) for i in range(10)])
    for strat in ("equal_layers", "balanced_flops", "balanced_latency"):
        p = partition(g, 4, strategy=strat)
        ranges = p.ranges()
        assert ranges[0][0] == 0 and ranges[-1][1] == 10
        assert all(hi > lo for lo, hi in ranges)            # non-empty
        assert all(a[1] == b[0] for a, b in zip(ranges, ranges[1:]))
        assert p.num_stages == 4


def test_balanced_flops_beats_equal_layers_on_skew():
    # one huge layer at the end: equal_layers puts it with others
    g = chain_graph([1e6] * 9 + [1e9])
    eq = partition(g, 4, strategy="equal_layers")
    bal = partition(g, 4, strategy="balanced_flops")
    assert max(s.flops for s in bal.stages) <= max(s.flops for s in eq.stages)


def test_balanced_latency_avoids_fat_cuts():
    # cutting after l1 would transfer a huge activation
    g = chain_graph([1e6] * 6, out_elems=[4, 1_000_000, 4, 4, 4, 4])
    p = partition(g, 2, strategy="balanced_latency",
                  link=LinkModel(bandwidth_bytes_per_s=1e6),
                  compute=ComputeModel(flops_per_s=1e9))
    assert 2 not in p.cuts      # cut index 2 = after node 1 (fat edge)


def test_heterogeneous_nodes_get_proportional_work():
    """Paper's future work: faster nodes receive more layers."""
    g = chain_graph([1e9] * 12)
    fast_last = [ComputeModel(10e9), ComputeModel(10e9), ComputeModel(40e9)]
    het = partition(g, 3, strategy="balanced_flops", compute=fast_last)
    sizes = [hi - lo for lo, hi in het.ranges()]
    assert sizes[2] > sizes[0]
    # the heterogeneous plan is never worse than the paper's equal split
    eq = partition(g, 3, strategy="equal_layers", compute=fast_last)
    assert het.bottleneck_s <= eq.bottleneck_s + 1e-12


@settings(max_examples=25, deadline=None)
@given(st.lists(st.floats(0.5, 50.0), min_size=3, max_size=7),
       st.lists(st.floats(1.0, 8.0), min_size=2, max_size=3))
def test_heterogeneous_dp_optimal_vs_brute_force(w, rates):
    k = len(rates)
    if k > len(w):
        return
    bounds = _linear_partition_dp(np.array(w), np.zeros(len(w)), k,
                                  np.array(rates))
    got = max(sum(w[lo:hi]) / rates[j]
              for j, (lo, hi) in enumerate(zip(bounds, bounds[1:])))
    best = float("inf")
    for cuts in itertools.combinations(range(1, len(w)), k - 1):
        bs = [0, *cuts, len(w)]
        best = min(best, max(sum(w[lo:hi]) / rates[j]
                             for j, (lo, hi) in enumerate(zip(bs, bs[1:]))))
    assert got <= best + 1e-9


def test_cut_cost_counts_pass_through():
    """An activation consumed two stages later crosses BOTH cuts."""
    g = LayerGraph("skip", jax.ShapeDtypeStruct((8,), np.float32))
    g.layer("a", lambda p, x: x, {}, ("",),
            jax.ShapeDtypeStruct((8,), np.float32), flops=1.0)
    g.layer("b", lambda p, x: x, {}, ("a",),
            jax.ShapeDtypeStruct((8,), np.float32), flops=1.0)
    g.layer("c", lambda p, x, y: x, {}, ("b", "a"),
            jax.ShapeDtypeStruct((8,), np.float32), flops=1.0)
    assert "a" in g.crossing_names(0)
    assert set(g.crossing_names(1)) == {"a", "b"}   # a passes through stage 2
    assert g.cut_cost(1) == 2 * 8 * 4


def test_explicit_cuts_override_strategy():
    g = chain_graph([1e6] * 8)
    p = partition(g, 3, cuts=(5, 7))
    assert p.ranges() == [(0, 5), (5, 7), (7, 8)]
    for bad in ((5,), (0, 4), (4, 8), (4, 4)):
        with pytest.raises(ValueError):
            partition(g, 3, cuts=bad)


def _costs(layer_s, bytes_=4.0, enc=0.0, dec=0.0):
    n = len(layer_s)
    return CalibratedCosts(
        layer_s=np.asarray(layer_s, np.float64),
        cut_bytes=np.full(n, bytes_), encode_s_per_byte=enc,
        decode_s_per_byte=dec, head_in_bytes=bytes_, tail_out_bytes=bytes_)


@settings(max_examples=40, deadline=None)
@given(st.lists(st.floats(0.01, 10.0), min_size=3, max_size=9),
       st.integers(2, 4))
def test_calibrated_dp_optimal_vs_brute_force(w, k):
    """The staged (max-of-stage-times) DP matches brute force."""
    if k > len(w):
        k = len(w)
    costs = _costs(w, enc=0.05, dec=0.03)
    bounds, got = calibrated_partition(costs, k)
    assert got == pytest.approx(bounds_bottleneck(costs, bounds))
    best = float("inf")
    for cuts in itertools.combinations(range(1, len(w)), k - 1):
        best = min(best, bounds_bottleneck(costs, [0, *cuts, len(w)]))
    assert got <= best + 1e-12


def test_warm_start_window_bounds_migration_size():
    """The warm-started DP shifts no boundary further than ``window``
    layers from the current cuts — the cap on weights a live migration
    ships — and still improves the bottleneck monotonically."""
    layer_s = [8.0] + [1.0] * 11               # heavy head layer
    costs = _costs(layer_s)
    cur = [0, 6, 9, 12]                        # skewed start
    full, full_b = calibrated_partition(costs, 3)
    windowed, win_b = calibrated_partition(costs, 3, prev_bounds=cur,
                                           window=2)
    for j in (1, 2):
        assert abs(windowed[j] - cur[j]) <= 2
    assert win_b <= bounds_bottleneck(costs, cur) + 1e-12
    assert full_b <= win_b + 1e-12             # full search at least as good
    # iterating windowed steps converges to the full optimum
    b = cur
    for _ in range(6):
        b, _ = calibrated_partition(costs, 3, prev_bounds=b, window=2)
    assert bounds_bottleneck(costs, b) == pytest.approx(full_b)


def test_warm_start_infeasible_window_falls_back():
    """A window too tight to form k non-empty stages falls back to the
    full search instead of failing.  Degenerate prev bounds (an empty
    stage, e.g. handed down from a different stage count) with window=0
    make every windowed plan infeasible, so this genuinely drives the
    dp[k][n] == INF fallback branch."""
    costs = _costs([1.0] * 6)
    bounds, got = calibrated_partition(costs, 3, prev_bounds=[0, 1, 1, 6],
                                       window=0)
    full, full_b = calibrated_partition(costs, 3)
    assert bounds == full and got == pytest.approx(full_b)
    assert all(b2 > b1 for b1, b2 in zip(bounds, bounds[1:]))
    # a valid window solves in-window without falling back
    wb, _ = calibrated_partition(costs, 3, prev_bounds=[0, 1, 2, 6],
                                 window=0)
    assert wb == [0, 1, 2, 6]


def test_calibrated_staged_prefers_overlap_aware_cuts():
    """Staged pricing is max(dec, cmp, enc), not the sum: a plan that
    equalizes stage compute at ~codec cost is optimal even though the
    sequential model would say codec makes it worse."""
    costs = _costs([1.0] * 8, bytes_=4.0, enc=0.25, dec=0.25)
    bounds, got = calibrated_partition(costs, 4, staged=True)
    assert got == pytest.approx(2.0)           # 2 layers/stage, codec hidden
    seq = bounds_bottleneck(costs, bounds, staged=False)
    assert seq > got                           # overlap is what buys it


def test_replica_pricing_amortizes_rate_not_latency():
    """Replicas divide a stage's throughput contribution (compute+codec),
    never a request's own service time."""
    g = chain_graph([1e6] * 8)
    p = partition(g, 2, cuts=(4,), replicas=(1, 2))
    assert p.replicas == (1, 2)
    s0, s1 = p.stages
    assert s1.throughput_service_s == pytest.approx(s1.service_time_s / 2)
    assert s0.throughput_service_s == pytest.approx(s0.service_time_s)
    # per-request bottleneck is replica-blind; the throughput one amortizes
    assert p.bottleneck_s == max(s0.service_time_s, s1.service_time_s)
    assert p.throughput_bottleneck_s <= p.bottleneck_s
    with pytest.raises(ValueError):
        partition(g, 2, cuts=(4,), replicas=(1, 2, 3))


def test_calibrated_replica_pricing():
    costs = _costs([1.0] * 8, enc=0.1, dec=0.1)
    one = costs.stage_service_s(0, 4)
    assert costs.stage_service_s(0, 4, replicas=2) == pytest.approx(one / 2)
    # bounds_bottleneck prices the replicated topology
    b = [0, 4, 8]
    assert bounds_bottleneck(costs, b, replicas=[2, 2]) == pytest.approx(
        bounds_bottleneck(costs, b) / 2)


def test_calibrated_dp_leans_layers_into_replicated_stage():
    """With stage 1 at 2 replicas, the replica-aware DP hands it ~2x the
    layers of stage 0 — a replica-blind plan would split evenly."""
    costs = _costs([1.0] * 9)
    blind, _ = calibrated_partition(costs, 2)
    aware, aware_b = calibrated_partition(costs, 2, replicas=[1, 2])
    assert blind[1] in (4, 5)
    assert aware[1] == 3                       # 3 layers vs 6/2 = 3 each
    assert aware_b == pytest.approx(3.0)
    # and the replica-aware plan is optimal under the replica ruler
    best = min(bounds_bottleneck(costs, [0, c, 9], replicas=[1, 2])
               for c in range(1, 9))
    assert aware_b <= best + 1e-12


def test_resnet_partition_reassembly_exact():
    from repro.models.cnn import resnet50
    g = resnet50(batch=1)
    params = g.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 224, 224, 3))
    full = g.apply(params, x)
    p = partition(g, 6, strategy="balanced_latency")
    acts = {"": x}
    out = None
    for lo, hi in p.ranges():
        nodes = g.slice_nodes(lo, hi)
        sub = {n: acts[n] for n in
               (g.crossing_names(lo - 1) if lo else [""])}
        for node in nodes:
            args = [sub[i] for i in node.inputs]
            sub[node.name] = node.fn(params[node.name], *args)
        exported = (g.crossing_names(hi - 1) if hi < len(g.nodes)
                    else [g.nodes[-1].name])
        acts.update({n: sub[n] for n in exported})
        out = sub[g.nodes[hi - 1].name]
    np.testing.assert_allclose(np.asarray(out), np.asarray(full), atol=1e-5)
