"""Every ResNet50 cell end to end through the harness on the CPU at tiny
size; the control and a broken timed path come out not correct."""
import numpy as np
import pytest

from bench import check, harness, spec
from bench.models import resnet

from _tiny import BENCH, result, run, window

CELLS = [w["name"] for w in BENCH["workloads"]
         if spec.load_config(BENCH, w["config"])["model"] == "resnet"]


@pytest.fixture(scope="module")
def wins():
    """One run's window per cell, shared by the tests below."""
    return {cell: window(cell) for cell in CELLS}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end(wins, cell):
    res = result(wins[cell])
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "compared"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    want = {m["name"] for m in harness.cell_metrics(BENCH, cell)["end_to_end"]}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    limit = wins[cell].config["correct"]["limit"]
    assert res["compared"]["max_rel_err"]["limit"] == limit
    assert res["compared"]["failed"] == {"value": 0, "limit": 0}


def test_control_fails_the_limit(wins):
    """The program (f32 on the CPU) reads near 0; the reference in the
    control's precision, put in its place, reads above the limit."""
    win = wins[CELLS[0]]
    corr = win.config["correct"]
    verdict = check.check(win, controls=(corr["control"],))
    assert verdict.passed and verdict.program < 1e-4
    assert verdict.controls[corr["control"]] > corr["limit"]


def test_an_answer_altered_where_it_is_produced_is_not_correct(monkeypatch):
    build = resnet.build_graph

    def broken(cfg):
        graph = build(cfg)
        fc = graph["fc"]
        orig = fc.fn
        fc.fn = lambda p, x: orig(p, x) * 1.1      # every logit 10% off
        return graph

    monkeypatch.setattr(resnet, "build_graph", broken)
    res = run("resnet50.c1")
    assert res["correct"] is False
    c = res["compared"]["max_rel_err"]
    assert c["value"] > c["limit"] and np.isfinite(c["value"])
