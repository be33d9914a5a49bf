"""Readings that the correctness limits are set from (``PERF.md`` §2).

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 1 2 3 ...

One process, one chip: for each seed, a run of the cell's own traffic at
its own size (set-up, a window of ``--seconds``), then the number the
check compares for the program and for each control in ``--controls``
(the reference in a lower precision in the program's place;
``bench/check.py``), and for decode cells the spread of the positions
the window's steps ran at.  One JSON line per
seed on standard output.  The benchmark's own runs never compute the
control.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _per_second(win) -> list[int]:
    """Requests, or tokens, completed in each second of the window."""
    times = [t for r in win.records for t in
             (r.times if hasattr(r, "times") else [r.t_done])]
    n = int(win.t_end - win.t0)
    counts = [0] * n
    for t in times:
        i = int(t - win.t0)
        if 0 <= i < n:
            counts[i] += 1
    return counts


def _quartiles(win) -> list[int] | None:
    """Min, quartiles and max of the positions of the decode steps whose
    tokens reached their clients in the window."""
    if win.traffic["kind"] != "decode":
        return None
    from bench import readers
    pos = sorted(readers.step_positions(win, win.t0, win.t_end))
    if not pos:
        return None
    return [pos[round(q * (len(pos) - 1))] for q in (0, .25, .5, .75, 1)]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="*", default=["bfloat16", "int8"],
                    help="control precisions to read (none: the program's "
                         "reading alone)")
    args = ap.parse_args()
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import check, harness, spec
    bench = spec.load_benchmark()
    cell = spec.find(bench["workloads"], args.workload, "workload")
    cfg = spec.load_config(bench, cell["config"])
    traffic = spec.load_traffic(cell["traffic"])

    import jax
    if jax.devices()[0].platform == "cpu":
        print("bench/calibrate.py: no accelerator", file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for seed in args.seeds:
        t0 = time.perf_counter()
        win = harness.serve_window(cell, cfg, traffic, seed, args.seconds,
                                   False, t0)
        t1 = time.perf_counter()
        verdict = check.check(win, controls=tuple(args.controls))
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "number": cfg["correct"]["number"],
            "program": verdict.program, "controls": verdict.controls,
            "attempted": len(win.records),
            "failed": sum(1 for r in win.records if r.error is not None),
            "window_compiles": win.compiles,
            "per_second": _per_second(win),
            "step_positions": _quartiles(win),
            "setup_s": win.setup_s, "check_s": time.perf_counter() - t1,
            "metrics": harness.end_to_end(
                win, harness.cell_metrics(bench, args.workload)["end_to_end"]),
        }), flush=True)
        del win, verdict
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
