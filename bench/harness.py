"""One run of one cell: set-up, the measured window, the correctness check
and the metrics.  ``bench/run.py`` is the command line around
:func:`run_cell`; tests call :func:`run_cell` directly with small
configurations and skip the look for a chip.

Order of a run:

1. set-up (``setup_s``, from process start): the engine over the
   configuration's chain, weights made on the device from the seed, the
   compiled programs (the persistent compile cache serves every run after
   a cell's first), and a warm-up of the cell's own shapes; then
   ``engine.reset_window()``;
2. the window: ``seconds`` of closed-loop traffic.  With ``trace``, the
   profiler records a few seconds in its middle;
3. device memory's peak is read, the engine shut down and its state freed;
4. the plain reference checks a sample, drawn from the seed, of what the
   window served;
5. the metrics: the cell's end-to-end metrics, or with ``trace`` its
   per-layer metrics.
"""
from __future__ import annotations

import dataclasses
import gc
import glob
import os
import tempfile
import threading
import time
from typing import Any

from bench import check, load, spec, weights

TRACE_SECONDS = 3.0
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts backend compiles (cache loads included) from JAX's own
    monitoring events while it is entered."""

    def __init__(self):
        self.events: list[tuple[float, str]] = []

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.events.append((time.perf_counter(), kw.get("fun_name", "")))

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)

    def between(self, t0: float, t1: float) -> list[str]:
        """Names of the programs compiled in ``[t0, t1]``."""
        return [name for t, name in self.events if t0 <= t <= t1]


class TraceWindow:
    """Profiles ``[start, stop]`` (perf_counter seconds) on a thread of its
    own, inside a ``bench.trace_window`` annotation, into ``log_dir``."""

    def __init__(self, start: float, stop: float, log_dir: str):
        self.start, self.stop, self.log_dir = start, stop, log_dir
        self.t0 = self.t1 = None
        self._thread = threading.Thread(target=self._run, name="bench-trace")
        self.error: BaseException | None = None

    def _run(self) -> None:
        import jax
        try:
            time.sleep(max(0.0, self.start - time.perf_counter()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("bench.trace_window"):
                    self.t0 = time.perf_counter()
                    time.sleep(max(0.0, self.stop - time.perf_counter()))
                    self.t1 = time.perf_counter()
            finally:
                jax.profiler.stop_trace()
        except BaseException as e:  # noqa: BLE001 - re-raised by join()
            self.error = e

    def begin(self) -> None:
        self._thread.start()

    def join(self) -> str:
        self._thread.join()
        if self.error is not None:
            raise self.error
        found = glob.glob(os.path.join(self.log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise RuntimeError(f"profiler wrote no trace under {self.log_dir}")
        return found[0]


@dataclasses.dataclass
class Window:
    """What one run measured, for the metric readers and the check."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    t0: float
    t_end: float
    records: list
    report: Any                      # EngineReport of the window
    compiles: list[str]              # programs compiled inside the window
    setup_s: float
    device: dict
    trace: Any = None                # trace_reduce.Reduced, traced runs
    trace_t: tuple[float, float] | None = None
    driver: Any = None


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def build_engine(graph, cfg: dict):
    from repro.runtime import InferenceEngine, TopologySpec
    from repro.runtime.dispatcher import DispatcherCodecs
    from repro.runtime.wire import WireCodec

    serving = cfg["serving"]
    topo = TopologySpec.chain(graph, serving["stages"],
                              strategy=serving["partition"])
    return InferenceEngine(graph, topo,
                           DispatcherCodecs(data=WireCodec(serving["codec"],
                                                           "none")),
                           max_batch=serving["max_batch"])


def serve_window(cell: dict, cfg: dict, traffic: dict, seed: int,
                 seconds: float, trace: bool, t_start: float) -> Window:
    """Steps 1-3 of a run; returns what the window measured."""
    import jax

    model = spec.model_module(cfg)
    if traffic["kind"] != model.KIND:
        raise ValueError(f"traffic kind {traffic['kind']!r} does not fit "
                         f"model {cfg['model']!r} ({model.KIND})")
    graph = model.build_graph(cfg)
    if traffic["kind"] == "oneshot":
        driver = load.Oneshot(traffic, model.input_shape(cfg), seed)
    else:
        driver = load.Decode(traffic, cfg, seed)
    with CompileCounter() as compiles:
        engine = build_engine(graph, cfg)
        try:
            engine.configure(weights.init_params(weights.param_specs(graph),
                                                 seed))
            engine.precompile()
            engine.start()
            driver.warm(engine, lambda: len(compiles.events))
            engine.reset_window()
            t0 = time.perf_counter()
            setup_s = t0 - t_start
            t_end = t0 + seconds
            tracer = None
            with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
                if trace:
                    span = min(TRACE_SECONDS, seconds / 2)
                    start = t0 + (seconds - span) / 2
                    tracer = TraceWindow(start, start + span, tdir)
                    tracer.begin()
                records = driver.window(engine, t_end)
                t_last = time.perf_counter()
                report = engine.report(wall_s=seconds)
                reduced = None
                if tracer is not None:
                    from bench.trace_reduce import reduce_file
                    reduced = reduce_file(tracer.join())
            dev = device_info(jax.devices())
            dev["memory_peak_bytes"] = max(
                (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in jax.devices()[:cell["chips"]])
            if reduced is not None:
                dev["busy_s"] = reduced.busy_s
                dev["window_s"] = reduced.window_s
        finally:
            engine.shutdown()
        del engine
    gc.collect()
    return Window(cell, cfg, traffic, seed, t0, t_end, records, report,
                  compiles.between(t0, t_last), setup_s, dev, reduced,
                  (tracer.t0, tracer.t1) if tracer is not None else None,
                  driver)


def end_to_end(win: Window, wanted: list[dict]) -> dict:
    if win.traffic["kind"] == "oneshot":
        values = load.oneshot_metrics(win.records, win.t0, win.t_end)
    else:
        values = load.decode_metrics(win.records, win.t0, win.t_end)
    values["setup_s"] = win.setup_s
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in wanted if m["name"] in values}


def per_layer(win: Window, wanted: list[dict]) -> dict:
    out = {}
    for m in wanted:
        value = spec.metric_module(m["name"]).read(win)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def finish(win: Window, metrics: dict, trace: bool) -> dict:
    """Steps 4-5 of a run: the result line's object.  ``metrics`` is
    ``{"end_to_end": [...], "per_layer": [...]}``, each already cut to
    this cell's entries."""
    verdict = check.check(win)
    failed = sum(1 for r in win.records if r.error is not None)
    result = {
        "correct": bool(verdict.passed and failed == 0),
        "attempted": len(win.records),
        "failed": failed,
        "metrics": (per_layer(win, metrics["per_layer"]) if trace
                    else end_to_end(win, metrics["end_to_end"])),
        "device": win.device,
    }
    if trace and win.trace is not None:
        result["breakdown"] = win.trace.breakdown()
    result["compared"] = {**verdict.compared,
                          "failed": {"value": failed, "limit": 0}}
    return result


def run_cell(cell: dict, cfg: dict, traffic: dict, metrics: dict, seed: int,
             seconds: float, trace: bool, t_start: float) -> dict:
    """A whole run; returns the result line's object."""
    win = serve_window(cell, cfg, traffic, seed, seconds, trace, t_start)
    return finish(win, metrics, trace)


def cell_metrics(bench: dict, cell_name: str) -> dict:
    """The end-to-end and per-layer entries of BENCHMARK.json that apply
    to one cell (those with no ``workloads`` list apply to every cell)."""
    def applies(m):
        return "workloads" not in m or cell_name in m["workloads"]
    return {"end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}

