"""The readers of ``resident_hop_share.*``: in a traced tiny run of their
own traffic kind they read the non-tail stages' hand-offs, all resident
over the in-process chain under the raw codec; they read nothing in the
other kind, or from a program without the ``resident`` counter."""
import dataclasses

import numpy as np
import pytest

from bench import spec

from _tiny import BENCH, window

NEW = ["resident_hop_share.oneshot", "resident_hop_share.decode"]
CELLS = ["resnet50.c1", "starcoder2-3b.s1"]


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
        assert value is not None and np.isfinite(value)
        assert value == 100.0           # every inner hop stays on the device
    else:
        assert value is None


@pytest.mark.parametrize("metric", NEW)
def test_reader_reports_nothing_without_the_counter(wins, metric):
    """The program before resident hops: its totals lack ``resident``."""
    entry = spec.find(BENCH["per_layer"], metric, "metric")
    win = wins[next(c for c in CELLS if c in entry["workloads"])]
    per_node = [dict(n, totals={k: v for k, v in n["totals"].items()
                                if k != "resident"})
                for n in win.report.per_node]
    report = dataclasses.replace(win.report, per_node=per_node)
    assert spec.metric_module(metric).read(
        dataclasses.replace(win, report=report)) is None


@pytest.mark.parametrize("metric", NEW)
def test_reader_reads_the_share_of_encoded_hand_offs(wins, metric):
    """Half the inner hand-offs encoded: the share reads 50; the tail's
    encodes never count."""
    entry = spec.find(BENCH["per_layer"], metric, "metric")
    win = wins[next(c for c in CELLS if c in entry["workloads"])]
    tail = max(n["stage"] for n in win.report.per_node)
    per_node = []
    for n in win.report.per_node:
        t = dict(n["totals"])
        if n["stage"] < tail:
            t["encodes"] = t["resident"]
        per_node.append(dict(n, totals=t))
    report = dataclasses.replace(win.report, per_node=per_node)
    assert spec.metric_module(metric).read(
        dataclasses.replace(win, report=report)) == pytest.approx(50.0)
