"""The trace reduction (bench/trace_reduce.py) against a hand count of a
small trace recorded on a TPU v5e: two decode sessions of a 2-layer
decoder (d_model 256, cache 1024) with the ``decode_attention`` kernel,
through a 2-stage serving chain, profiled for ~0.1 s.

The expected numbers were counted from the same file independently of
the reduction: busy time by marking a 10 ns timeline wherever an
``XLA Ops`` event runs, the kernel's time by adding up the durations of
the ``%decode_attention`` events, its staging copies by adding up the
``%copy`` events with its cache's shape, the longest gap as the longest
unmarked run of that timeline.
"""
import os

import pytest

from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "decode_chain_v5e.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return tr.Trace.load(DATA)


@pytest.fixture(scope="module")
def reduced(trace):
    return tr.reduce_trace(trace)


def test_window_falls_back_to_the_device_events(trace):
    t0, t1 = trace.window()
    assert (t0, t1) == (43685401.0, 145125677.0)


def test_busy_and_idle_share_match_the_hand_count(reduced):
    assert reduced.devices == 1
    assert reduced.window_s == pytest.approx(0.101440276)
    assert reduced.busy_s == pytest.approx(0.0010587, rel=0.01)
    assert reduced.idle_share == pytest.approx(0.98956, abs=2e-4)


def test_kernel_time_by_stable_name(reduced):
    assert reduced.kernel_calls["decode_attention"] == 44
    assert reduced.kernel_s["decode_attention"] == pytest.approx(78.223e-6)


def test_staging_copies_of_the_kernel_operands(reduced):
    # hand count: the 176 ``%copy`` events with an f32[1,1024,2,128]
    # result, four per kernel call (K and V into the kernel's layout in
    # VMEM, and back), 329.45 us in all
    assert reduced.staging_s == {"decode_attention": pytest.approx(329.45e-6)}


def test_cache_operands_are_the_largest_arrays_of_the_call():
    event = ("%decode_attention.1 = f32[1,2,2,128]{3,2,1,0:T(2,128)S(1)} "
             "custom-call(s32[1]{0:T(128)} %copy.11, f32[1,2,2,128]{3,2,1,0} "
             "%bitcast.37, f32[1,2,1024,128]{3,2,1,0:T(8,128)S(1)} "
             "%bitcast.27, s32[1,1,1024]{2,1,0} %bitcast.34)")
    assert tr.cache_operands(event) == {("f32", 262144)}


def test_breakdown_names_the_top_ops_and_the_longest_gaps(reduced):
    b = reduced.breakdown()
    assert len(b["device_ops"]) == len(b["idle_gaps"]) == tr.TOP
    name, seconds = b["device_ops"][0]
    assert name == "copy.5 f32[1,1024,2,128]"
    assert seconds == pytest.approx(117.612e-6)
    label, gap = b["idle_gaps"][0]
    assert gap == pytest.approx(4.78954e-3, abs=2e-8)
    assert label == "np.asarray(jax.Array)"
    gaps = [g for _, g in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)


def test_a_window_span_bounds_the_reduction(trace):
    t0, t1 = trace.window()
    mid = (t0 + t1) / 2
    cut = tr.Trace(trace.device_ops,
                   trace.host + [(t0, mid, tr.WINDOW_SPAN)])
    half = tr.reduce_trace(cut)
    assert half.window_s == pytest.approx((mid - t0) / 1e9)
    assert 0 < half.busy_s < tr.reduce_trace(trace).busy_s


@pytest.mark.parametrize("event,stable,label", [
    ("%decode_attention.1 = f32[1,2,2,128]{3,2,1,0} custom-call(s32[1]{0})",
     "decode_attention", "decode_attention.1 f32[1,2,2,128]"),
    ("%fusion.91 = f32[8,112,112,64]{3,0,2,1} fusion(bf16[8])",
     "fusion", "fusion.91 f32[8,112,112,64]"),
    ("%copy-start = (f32[3]{0}, u32[]) copy-start(f32[3]{0} %p)",
     "copy-start", "copy-start (f32[3]"),
])
def test_op_names(event, stable, label):
    assert tr.op_name(event) == stable
    assert tr.op_label(event) == label


def test_merge_and_gaps():
    busy = tr.merge([(5, 7), (0, 2), (1, 3), (9, 20)], 0, 10)
    assert busy == [(0, 3), (5, 7), (9, 10)]
    assert tr.gaps(busy, 0, 10) == [(3, 5), (7, 9)]
