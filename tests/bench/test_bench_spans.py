"""The program's spans and counters as the benchmark reads them: the
``defer.*`` annotations land on a profiler trace's host lines, where the
reduction labels idle gaps with them, and the readers of the span and
wait metrics report a number in traced runs of their cells and nothing
outside them or from a program that keeps no totals."""
import dataclasses
import glob

import jax
import numpy as np
import pytest

from bench import spec
from bench import trace_reduce as tr
from repro.runtime import InferenceEngine
from repro.runtime.dispatcher import DispatcherCodecs
from repro.runtime.wire import WireCodec
from tests._worker_graphs import mlp_graph

from _tiny import BENCH, window

D = 32
NEW = ["kv_move_ms.decode", "host_copy_ms.oneshot", "host_copy_ms.decode",
       "queue_wait_ms.oneshot", "queue_wait_ms.decode"]
# one cell of each traffic kind: the readers look at the kind and at the
# program's totals, which the cells of one kind share
CELLS = ["resnet50.c1", "starcoder2-3b.s1"]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A 2-stage MLP chain serving 4 requests one after another under
    the profiler, on the CPU; the trace as the reduction loads it."""
    g = mlp_graph(4, D)
    raw = WireCodec("raw", "none")
    eng = InferenceEngine(g, 2, DispatcherCodecs(data=raw, weights=raw))
    eng.configure(g.init(jax.random.PRNGKey(0)))
    eng.start()
    eng.submit(np.zeros((1, D), np.float32)).result(timeout=60)
    out = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(out)):
        for i in range(4):
            eng.submit(np.full((1, D), i, np.float32)).result(timeout=60)
    eng.shutdown()
    path = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)[0]
    return tr.Trace.load(path)


def test_spans_land_on_the_host_lines(traced):
    names = {n for _, _, n in traced.host if n.startswith("defer.")}
    want = {f"defer.stage{i}.{w}" for i in (0, 1)
            for w in ("deserialize", "h2d", "apply", "d2h", "serialize")}
    want |= {"defer.dispatcher.serialize", "defer.dispatcher.collect"}
    assert want <= names
    assert not {n for n in names if "wait" in n}     # waits are counted only


def test_a_gap_a_span_covers_is_labelled_with_it(traced):
    s, e, name = max((h for h in traced.host
                      if h[2] == "defer.stage1.deserialize"),
                     key=lambda h: h[1] - h[0])
    mid, half = (s + e) / 2, (e - s) / 4
    assert tr._label((mid - half, mid + half), traced.host) == name


@pytest.fixture(scope="module")
def wins():
    """One traced tiny run per cell."""
    return {cell: window(cell, seconds=1.5, trace=True) for cell in CELLS}


@pytest.mark.parametrize("metric", NEW)
@pytest.mark.parametrize("cell", CELLS)
def test_reader_reads_its_cells_and_only_them(wins, cell, metric):
    entry = spec.find(BENCH["per_layer"], metric, "metric")
    value = spec.metric_module(metric).read(wins[cell])
    if cell in entry["workloads"]:
        assert value is not None and np.isfinite(value) and value > 0
    else:
        assert value is None


@pytest.mark.parametrize("metric", NEW)
def test_reader_reports_nothing_from_a_program_without_totals(wins, metric):
    """The program before its spans: no ``totals`` per replica and no
    dispatcher totals in the report."""
    entry = spec.find(BENCH["per_layer"], metric, "metric")
    win = wins[next(c for c in CELLS if c in entry["workloads"])]
    per_node = [{k: v for k, v in n.items() if k != "totals"}
                for n in win.report.per_node]
    report = dataclasses.replace(win.report, per_node=per_node,
                                 dispatcher={}, session={})
    assert spec.metric_module(metric).read(
        dataclasses.replace(win, report=report)) is None
