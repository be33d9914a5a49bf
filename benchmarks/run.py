"""Benchmark runner: one harness per paper table/figure + the roofline.

    PYTHONPATH=src python -m benchmarks.run [--only name,name]

Emits CSV blocks to stdout and artifacts/bench/*.csv.  The roofline table
reads artifacts/dryrun/*.json (produced by ``repro.launch.dryrun --all``);
missing artifacts are reported, not fatal.
"""
from __future__ import annotations

import argparse
import sys
import time

from repro.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    args = ap.parse_args()

    from benchmarks import (fig2_throughput, fig3_energy, pipeline_wire,
                            roofline, table1_codecs, table2_codec_throughput)
    benches = {
        "fig2_throughput": fig2_throughput.main,
        "table1_codecs": table1_codecs.main,
        "table2_codec_throughput": table2_codec_throughput.main,
        "fig3_energy": fig3_energy.main,
        "pipeline_wire": pipeline_wire.main,
        "roofline": roofline.main,
    }
    names = args.only.split(",") if args.only else list(benches)
    failed = []
    for name in names:
        t0 = time.perf_counter()
        try:
            benches[name]()
            print(f"# {name} done in {time.perf_counter()-t0:.1f}s\n")
        except Exception as e:  # deferlint: swallow(recorded in failed[]; run exits nonzero below)
            failed.append(name)
            print(f"# {name} FAILED: {type(e).__name__}: {e}\n")
    if failed:
        sys.exit(f"benchmarks failed: {failed}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
