"""The Mamba2 + attention hybrid's cell end to end through the harness on
the CPU at a tiny size of its own: correct, with its per-layer metrics
read; a step that leaves the recurrent state unchanged and the int8
control come out not correct."""
import dataclasses
import time

import numpy as np
import pytest

from bench import check, harness, load, peaks, spec
from bench.models import hybrid_lm

BENCH = spec.load_benchmark()
CELL = "granite-4.0-h-micro.s16"
SEED = 2**33 + 54321          # a seed beyond 32 bits, as the driver's are


def tiny() -> tuple[dict, dict, dict]:
    """The cell's configuration and traffic with every key kept and only
    sizes cut: width 64, heads of 16 (4 query, 2 KV), Mamba2 8 heads of
    16 with a state of 16 and chunks of 16, a 1024-token vocabulary, a
    64-slot cache, the whole first period of ten layers; 3 sessions,
    prompts 4-16, a wave of at most 4."""
    cell = spec.find(BENCH["workloads"], CELL, "workload")
    cfg = spec.load_config(BENCH, cell["config"])
    traffic = spec.load_traffic(cell["traffic"])
    cfg.update(vocab_size=1024, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, intermediate_size=96,
               shared_intermediate_size=96, mamba_n_heads=8, mamba_d_head=16,
               mamba_d_state=16, mamba_chunk_size=16,
               max_position_embeddings=64)
    cfg["serving"]["max_batch"] = 4
    traffic.update(sessions=3, pool=5, prompt_median=8, prompt_min=4,
                   prompt_max=16, warmup_tokens=3)
    return cell, cfg, traffic


def window(seconds: float = 1.5, trace: bool = False) -> harness.Window:
    cell, cfg, traffic = tiny()
    return harness.serve_window(cell, cfg, traffic, SEED, seconds, trace,
                                time.perf_counter())


def result(win, trace: bool = False) -> dict:
    return harness.finish(win, harness.cell_metrics(BENCH, CELL), trace)


@pytest.fixture(scope="module")
def win():
    """One traced run's window, read both ways by the tests below."""
    return window(seconds=2.0, trace=True)


def test_hybrid_cell_runs_end_to_end_and_is_correct(win):
    res = result(win)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0 and res["device"]["platform"] == "cpu"
    assert set(res["metrics"]) == {"tokens_per_s", "itl_p95_ms", "setup_s"}
    assert res["compared"]["max_logit_gap"]["value"] < 1e-4


def test_traced_hybrid_run_reports_its_per_layer_metrics(win, monkeypatch):
    # the CPU has no published peak; borrow the v5e's so that the readers
    # that take a share of a peak have one (the numbers mean nothing here)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    res = result(win, trace=True)
    assert res["correct"] is True
    m = res["metrics"]
    assert {"wave_rows.decode", "stage_compute_ms.decode",
            "device_idle.decode", "window_compiles.decode",
            "mfu.hybrid_decode", "slab_rows.decode"} <= set(m)
    assert m["window_compiles.decode"]["value"] == 0
    # both replicas keep session_capacity + 1 rows: the CPU gives no
    # memory figures to budget by
    assert m["slab_rows.decode"]["value"] == 65
    assert m["mfu.hybrid_decode"]["value"] > 0
    totals = [n["totals"] for n in win.report.per_node]
    # stage 0 holds Mamba2 layers only, stage 1 the attention layer too
    assert totals[0]["kv_bytes"] == 0 < totals[0]["state_bytes"]
    assert totals[1]["kv_bytes"] > 0 and totals[1]["state_bytes"] > 0
    assert all(t["kv_evictions"] == 0 for t in totals)


def _greedy_sessions(win) -> list:
    """Every pool context decoded greedily by the reference to the end of
    its cache, as served sessions (as ``test_bench_cells_decode.py``
    reads its control)."""
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


def test_hybrid_control_fails_the_limit(win):
    """The reference in the control's precision (int8 operands), put in
    the program's place, reads above the limit, over every pool context
    decoded to a full cache."""
    sessions = _greedy_sessions(win)
    corr = dict(win.config["correct"], sessions=len(sessions))
    w = dataclasses.replace(win, records=sessions,
                            config=dict(win.config, correct=corr))
    verdict = check.check(w, controls=(corr["control"],))
    assert verdict.passed and verdict.program < 1e-4
    assert verdict.controls[corr["control"]] > corr["limit"]


def test_a_step_that_leaves_the_state_unchanged_is_not_correct(monkeypatch):
    """A timed path whose Mamba2 steps return the slab they were given (the
    state never advances past the prefill) is caught."""
    build = hybrid_lm.build_graph

    def broken(cfg):
        graph = build(cfg)
        for node in graph.nodes:
            if node.decode is None or not node.decode.recurrent:
                continue
            step = node.decode.step_fn

            def frozen(p, cache, x, pos, step=step):
                y, _ = step(p, cache, x, pos)
                return y, cache
            node.decode.step_fn = frozen
        return graph

    monkeypatch.setattr(hybrid_lm, "build_graph", broken)
    res = result(window())
    assert res["correct"] is False
    c = res["compared"]["max_logit_gap"]
    assert c["value"] > c["limit"] and np.isfinite(c["value"])
