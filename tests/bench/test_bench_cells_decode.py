"""Every decode cell end to end through the harness on the CPU at tiny
size, traced once; the control and a broken timed path come out not
correct."""
import dataclasses

import numpy as np
import pytest

from bench import check, harness, load, peaks, spec
from bench.models import decode_lm

from _tiny import BENCH, result, run, window

CELLS = [w["name"] for w in BENCH["workloads"]
         if spec.load_config(BENCH, w["config"])["model"] == "decode_lm"]


@pytest.fixture(scope="module")
def wins():
    """One run's window per cell, shared by the tests below."""
    return {cell: window(cell) for cell in CELLS}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end(wins, cell):
    res = result(wins[cell])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    want = {m["name"] for m in harness.cell_metrics(BENCH, cell)["end_to_end"]}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "compared"


def test_traced_run_reports_its_per_layer_metrics(monkeypatch):
    # the CPU has no published peak; borrow the v5e's so that the readers
    # that take a share of a peak have one (the numbers mean nothing here)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    res = run("starcoder2-3b.s16", seconds=2.0, trace=True)
    assert res["correct"] is True
    names = set(res["metrics"])
    assert {"wave_rows.decode", "stage_compute_ms.decode", "mfu.decode",
            "device_idle.decode", "window_compiles.decode"} <= names
    assert res["metrics"]["window_compiles.decode"]["value"] == 0
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def _greedy_sessions(win) -> list:
    """Every pool context of ``win`` decoded greedily by the reference to
    the end of its cache, as served sessions: a fixed set of tokens, the
    same however many the window's own sessions reached."""
    ref = spec.reference_module(win.config["name"])
    params = check._params(win)
    cap = win.config["max_position_embeddings"]
    out = []
    for p, prompt in enumerate(win.driver.prompts):
        seq, tokens = [int(t) for t in prompt], []
        while len(seq) < cap:
            tokens.append(int(ref.logits(params, win.config, seq)[-1].argmax()))
            seq.append(tokens[-1])
        out.append(load.Session(0, p, p, 0.0, tokens, [0.0] * len(tokens)))
    return out


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limit(wins, cell):
    """The program (f32 on the CPU) agrees with the reference at every
    served token; the reference in the control's precision, put in its
    place, does not.  A widest gap swings with the tokens it is read over,
    so at this size it is read over every pool context decoded to a full
    cache."""
    win = wins[cell]
    assert check.check(win).program < 1e-4
    sessions = _greedy_sessions(win)
    corr = dict(win.config["correct"], sessions=len(sessions))
    win = dataclasses.replace(win, records=sessions,
                              config=dict(win.config, correct=corr))
    verdict = check.check(win, controls=(corr["control"],))
    assert verdict.passed and verdict.program < 1e-4
    assert verdict.controls[corr["control"]] > corr["limit"]


def _break(monkeypatch, wrap):
    build = decode_lm.build_graph

    def broken(cfg):
        graph = build(cfg)
        wrap(graph)
        return graph

    monkeypatch.setattr(decode_lm, "build_graph", broken)


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    def wrap(graph):
        head = graph["head"]
        orig = head.fn
        # a spike on token 7 wherever the head runs: every served token is 7
        head.fn = lambda p, x: orig(p, x).at[..., 7].add(100.0)

    _break(monkeypatch, wrap)
    res = run("starcoder2-3b.s1")
    assert res["correct"] is False
    c = res["compared"]["max_logit_gap"]
    assert c["value"] > c["limit"] and np.isfinite(c["value"])


def test_a_step_that_returns_its_cache_unchanged_is_not_correct(monkeypatch):
    def wrap(graph):
        for node in graph.nodes:
            if node.decode is None:
                continue
            step = node.decode.step_fn

            def frozen(p, cache, x, pos, step=step):
                y, _ = step(p, cache, x, pos)
                return y, cache
            node.decode.step_fn = frozen

    _break(monkeypatch, wrap)
    res = run("starcoder2-3b.s1")
    assert res["correct"] is False
    c = res["compared"]["max_logit_gap"]
    assert c["value"] > c["limit"]
