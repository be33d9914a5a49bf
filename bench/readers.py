"""Shared arithmetic of the per-layer metric readers (``bench/metrics``):
what they take from the engine's report, the trace and the window's
records."""
from __future__ import annotations

from bench import work
from bench.peaks import peak_for


def nodes(win) -> list[dict]:
    """The report's per-replica entries that served anything."""
    return [n for n in win.report.per_node if n["requests"]]


def batches(node: dict) -> float:
    """Merged batches (``BatchTrace`` records) a replica computed."""
    return node["requests"] / node["batch_mean"]


def mean_rows(win) -> float | None:
    """Mean ``BatchTrace.n`` over every stage's batches in the window."""
    ns = nodes(win)
    if not ns:
        return None
    return sum(n["requests"] for n in ns) / sum(batches(n) for n in ns)


def busiest(win) -> dict | None:
    """The replica with the most compute time in the window."""
    ns = nodes(win)
    return max(ns, key=lambda n: n["compute_s"] * n["requests"]) if ns else None


def step_positions(win, t0: float, t1: float) -> list[int]:
    """Position of every decode step whose token reached its client in
    ``[t0, t1]``: a session's token ``i >= 1`` comes from the step that
    fed token ``i - 1`` at position ``len(prompt) + i - 1``."""
    out = []
    for s in win.records:
        p = len(win.driver.prompts[s.prompt])
        out.extend(p + i - 1 for i, t in enumerate(s.times)
                   if i >= 1 and t0 <= t <= t1)
    return out


def token_positions(win) -> list[int]:
    """Position each yielded token in the window was computed at (the
    first token of a session: the prompt's last position)."""
    out = []
    for s in win.records:
        p = len(win.driver.prompts[s.prompt])
        out.extend(p + i - 1 for i, t in enumerate(s.times) if t <= win.t_end)
    return out


def peak(win):
    return peak_for(win.device["kind"])


def idle_percent(win) -> float | None:
    if win.trace is None:
        return None
    return 100.0 * win.trace.idle_share


def decode_mfu(win) -> float:
    seconds = win.t_end - win.t0
    flops = sum(work.decode_flops_per_token(win.config, p)
                for p in token_positions(win))
    return 100.0 * flops / seconds / peak(win).flops_per_s
