"""Run one benchmark cell on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics come from
``BENCHMARK.json`` at the root of the checkout.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` a ``breakdown``, and
last ``compared``: each number the correctness check compared, beside its
limit.  The same comparisons are the last lines of standard error.

Exits non-zero, printing no result, when JAX finds no accelerator or fewer
chips than the cell asks for, when the checkout lacks the program
(``src/repro``), or when anything in the run raises.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def fail(msg: str) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr, flush=True)
    return 1


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"the program (src/repro) is not in the checkout {ROOT}")
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)

    from bench import harness, spec
    bench = spec.load_benchmark()
    cell = spec.find(bench["workloads"], args.workload, "workload")
    cfg = spec.load_config(bench, cell["config"])
    traffic = spec.load_traffic(cell["traffic"])

    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        return fail(f"no accelerator: JAX {jax.__version__} sees "
                    f"{len(devices)} cpu device(s)")
    if len(devices) < cell["chips"]:
        return fail(f"{args.workload} needs {cell['chips']} chips, JAX sees "
                    f"{len(devices)}")

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program, however quick to compile, goes into the cache, so a
    # cell's second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    try:
        win = harness.serve_window(cell, cfg, traffic, args.seed,
                                   args.seconds, bool(args.trace), T_START)
        print(f"programs compiled in the window: {win.compiles}",
              file=sys.stderr, flush=True)
        result = harness.finish(win, harness.cell_metrics(bench, args.workload),
                                bool(args.trace))
    except Exception:  # noqa: BLE001 - reported, and no result line
        traceback.print_exc()
        return fail(f"{args.workload} seed {args.seed} raised; no result")
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
