"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

What is read, and how (the layout of a TPU trace as JAX 0.9 writes it):

- Device planes are named ``/device:TPU:<n>``.  Their ``XLA Ops`` line
  holds one event per HLO operation that ran, named by the HLO text
  (``%fusion.91 = f32[8,112,112,64]{...} fusion(...)``); a Pallas kernel
  is a custom call named after the kernel (``%decode_attention.1 = ...
  custom-call(...)``).  ``Async XLA Ops`` (DMA spans that overlap compute)
  are not counted as busy.
- The host plane ``/host:CPU`` has one line per thread.  Its events are
  the runtime's own (``np.asarray(jax.Array)``, ``PjitFunction(...)``,
  ``XlaLinearize``) and the harness's ``TraceAnnotation``s, all named
  ``bench.*``.  Python-tracer events (``$file:line fn``) are skipped.
- Device and host events share one clock.

The window is the span of the harness's ``bench.trace_window``
annotation where there is one, else the extent of all device events.
Busy time is the union of the device's op intervals inside the window,
averaged over the devices that ran anything; an idle gap is a stretch of
the window in which no op ran, labelled with the host event that
overlaps it most (the runtime's events first, the harness's only where no
other event overlaps the gap; spans of half the window or more, such as
an enclosing annotation, are not labels).
"""
from __future__ import annotations

import collections
import dataclasses
import re

WINDOW_SPAN = "bench.trace_window"
HARNESS_PREFIX = "bench."
TOP = 10

_SUFFIX = re.compile(r"\.\d+$")
_ARRAY = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")


def op_name(event_name: str) -> str:
    """The stable name of an HLO op event: ``%decode_attention.1 = ...``
    -> ``decode_attention``; ``%fusion.91 = ...`` -> ``fusion``."""
    head = event_name.split(" = ", 1)[0].lstrip("%").strip()
    return _SUFFIX.sub("", head)


def op_label(event_name: str) -> str:
    """A readable label of one HLO op: its instruction name and result
    shape, ``fusion.91 f32[8,112,112,64]``."""
    head, _, rest = event_name.partition(" = ")
    shape = re.split(r"[{ ]", rest.strip(), maxsplit=1)[0] if rest else ""
    return f"{head.lstrip('%').strip()} {shape}".strip()


def _arrays(text: str) -> list[tuple[str, int]]:
    """(dtype, element count) of every array shape in ``text``."""
    out = []
    for dtype, dims in _ARRAY.findall(text):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        out.append((dtype, n))
    return out


def cache_operands(event_name: str) -> set[tuple[str, int]]:
    """The largest array operands of a custom call (a Pallas kernel):
    ``(dtype, element count)`` of what it streams, such as a KV cache."""
    _, _, rest = event_name.partition(" = ")
    _, _, args = rest.partition("custom-call(")
    arrays = _arrays(args)
    if not arrays:
        return set()
    top = max(n for _, n in arrays)
    return {a for a in arrays if a[1] == top}


def merge(intervals: list[tuple[float, float]], t0: float, t1: float
          ) -> list[tuple[float, float]]:
    """Sorted union of ``intervals`` clipped to ``[t0, t1]``."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, t0), min(e, t1)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: list[tuple[float, float]], t0: float, t1: float
         ) -> list[tuple[float, float]]:
    out, cur = [], t0
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        out.append((cur, t1))
    return out


@dataclasses.dataclass
class Trace:
    """The events of one trace that the reduction needs, in ns."""

    device_ops: dict[str, list[tuple[float, float, str]]]   # plane -> ops
    host: list[tuple[float, float, str]]                    # (start, end, name)

    @classmethod
    def load(cls, path: str) -> "Trace":
        import jax
        pd = jax.profiler.ProfileData.from_file(path)
        device_ops: dict[str, list] = {}
        host: list = []
        for plane in pd.planes:
            if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        ops = device_ops.setdefault(plane.name, [])
                        ops.extend((e.start_ns, e.start_ns + e.duration_ns,
                                    e.name) for e in line.events)
            elif plane.name.startswith("/host:CPU"):
                for line in plane.lines:
                    host.extend((e.start_ns, e.start_ns + e.duration_ns,
                                 e.name) for e in line.events
                                if not e.name.startswith("$"))
        return cls({k: v for k, v in device_ops.items() if v}, host)

    def window(self) -> tuple[float, float]:
        spans = [(s, e) for s, e, n in self.host if n == WINDOW_SPAN]
        if spans:
            return min(s for s, _ in spans), max(e for _, e in spans)
        ops = [o for v in self.device_ops.values() for o in v]
        if not ops:
            raise ValueError("trace has no window span and no device op")
        return min(o[0] for o in ops), max(o[1] for o in ops)


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                       # mean over devices that ran ops
    devices: int
    kernel_s: dict[str, float]          # stable op name -> seconds
    kernel_calls: dict[str, int]
    # custom call -> seconds of the ``copy`` ops whose result has the dtype
    # and size of its largest operands: XLA staging them into the kernel's
    # layout (and memory space) and back, work the kernel's own time leaves out
    staging_s: dict[str, float]
    device_ops: list[tuple[str, float]]  # top ops by time, seconds
    idle_gaps: list[tuple[str, float]]   # longest gaps, seconds

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.device_ops],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps]}


def _label(gap: tuple[float, float], host: list[tuple[float, float, str]]
           ) -> str:
    g0, g1 = gap
    best: dict[bool, tuple[float, str]] = {}
    for s, e, name in host:
        ov = min(e, g1) - max(s, g0)
        if ov <= 0 or name == WINDOW_SPAN:
            continue
        ours = name.startswith(HARNESS_PREFIX)
        if ov > best.get(ours, (0.0, ""))[0]:
            best[ours] = (ov, name)
    if False in best:
        return best[False][1]
    if True in best:
        return best[True][1]
    return "no host event"


def reduce_trace(trace: Trace) -> Reduced:
    t0, t1 = trace.window()
    busy_total = 0.0
    kernel_ns: collections.Counter = collections.Counter()
    kernel_n: collections.Counter = collections.Counter()
    op_ns: collections.Counter = collections.Counter()
    copy_ns: collections.Counter = collections.Counter()
    operands: dict[str, set] = {}
    all_gaps: list[tuple[float, float]] = []
    for ops in trace.device_ops.values():
        inside = [(s, e, n) for s, e, n in ops if e > t0 and s < t1]
        busy = merge([(s, e) for s, e, _ in inside], t0, t1)
        busy_total += sum(e - s for s, e in busy)
        all_gaps.extend(gaps(busy, t0, t1))
        for s, e, n in inside:
            d = min(e, t1) - max(s, t0)
            name = op_name(n)
            kernel_ns[name] += d
            kernel_n[name] += 1
            op_ns[op_label(n)] += d
            if name == "copy":
                result = _arrays(n.partition(" = ")[2])[:1]
                if result:
                    copy_ns[result[0]] += d
            elif "custom-call(" in n:
                operands.setdefault(name, set()).update(cache_operands(n))
    devices = max(1, len(trace.device_ops))
    # spans as long as half the window (a thread's lifetime, an enclosing
    # annotation) say nothing about what the host did in one gap
    host = [h for h in trace.host
            if h[1] > t0 and h[0] < t1 and h[1] - h[0] < (t1 - t0) / 2]
    longest = sorted(all_gaps, key=lambda g: g[1] - g[0], reverse=True)[:TOP]
    return Reduced(
        window_s=(t1 - t0) / 1e9,
        busy_s=busy_total / devices / 1e9,
        devices=len(trace.device_ops),
        kernel_s={k: v / 1e9 for k, v in kernel_ns.items()},
        kernel_calls=dict(kernel_n),
        staging_s={k: sum(copy_ns[a] for a in arrays) / 1e9
                   for k, arrays in operands.items()},
        device_ops=[(n, v / 1e9) for n, v in op_ns.most_common(TOP)],
        idle_gaps=[(_label(g, host), (g[1] - g[0]) / 1e9) for g in longest])


def reduce_file(path: str) -> Reduced:
    return reduce_trace(Trace.load(path))
