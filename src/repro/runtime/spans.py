"""Running totals of the serving chain: spans, hand-off waits, counters.

Each owner — a compute replica (``stage<i>``), the ``dispatcher``, the
client-side ``session`` loop — keeps one :class:`Spans`: a flat dict of
window totals, reset with the measurement window, under the lock its
owner already uses for its stats.

* A **span** times one piece of work: its ``perf_counter`` duration and a
  count go into ``<what>_s`` / ``<what>_n``, and while a profiler runs it
  is also a ``jax.profiler.TraceAnnotation`` named
  ``defer.<owner>.<what>`` (built once, here), carrying the request id
  (or a wave's first id and row count) as metadata, so it lands on the
  device trace's clock.  With no profiler running the annotation costs
  one check and no string is formatted.
* A **wait** is the time an item sat in an in-process queue, measured at
  dequeue from a stamp set at enqueue (``wait_<handoff>_s`` / ``_n``,
  ``n`` counting requests).  Waits are counted, never annotated: a
  blocked thread's span would label every idle gap of a trace.  An item
  that crossed a process boundary carries no stamp; its wait is missing,
  not zero.
* A **counter** is a plain running sum (rows, bytes, waves).
* A **gauge** is a value set, not summed, that the window's reset keeps:
  what the owner holds rather than what it did (a slab's rows and bytes).
"""
from __future__ import annotations

import threading
import time

from jax.profiler import TraceAnnotation

_tracing = TraceAnnotation.is_enabled


class Spans:
    def __init__(self, owner: str, spans: tuple = (), waits: tuple = (),
                 counters: tuple = (), lock: threading.Lock | None = None,
                 gauges: tuple = ()):
        # (annotation name, seconds key, count key): formatted once here
        self._spans = {w: (f"defer.{owner}.{w}", f"{w}_s", f"{w}_n")
                       for w in spans}
        self._waits = {h: (f"wait_{h}_s", f"wait_{h}_n") for h in waits}
        self.lock = lock if lock is not None else threading.Lock()
        keys = [k for v in self._spans.values() for k in v[1:]]
        keys += [k for v in self._waits.values() for k in v]
        self.gauges = tuple(gauges)
        self.zero = dict.fromkeys(keys + list(counters) + list(gauges), 0)
        self.totals = dict(self.zero)

    def span(self, what: str, **meta) -> "_Span":
        """``with spans.span("apply", rid=..., rows=...):`` — ``meta`` is
        the annotation's metadata."""
        return _Span(self, self._spans[what], meta)

    def add(self, **incs) -> None:
        with self.lock:
            self.add_locked(**incs)

    def add_locked(self, **incs) -> None:
        """:meth:`add` for a caller that already holds :attr:`lock`."""
        t = self.totals
        for k, v in incs.items():
            t[k] += v

    def waited(self, handoff: str, stamp: float | None, n: int,
               now: float | None = None) -> None:
        """One dequeue: ``n`` requests that were stamped at ``stamp``."""
        if stamp is None:
            return
        dt = (time.perf_counter() if now is None else now) - stamp
        ks, kn = self._waits[handoff]
        with self.lock:
            self.totals[ks] += dt
            self.totals[kn] += n

    def set(self, **values) -> None:
        """Set gauges."""
        with self.lock:
            self.totals.update(values)

    def reset(self) -> None:
        with self.lock:
            self.totals = {**self.zero,
                           **{g: self.totals[g] for g in self.gauges}}

    def snapshot(self) -> dict:
        with self.lock:
            return dict(self.totals)


class _Span:
    __slots__ = ("_owner", "_keys", "_meta", "_ann", "_t0")

    def __init__(self, owner: Spans, keys: tuple, meta: dict):
        self._owner, self._keys, self._meta = owner, keys, meta

    def __enter__(self) -> None:
        self._ann = None
        if _tracing():
            self._ann = TraceAnnotation(self._keys[0], **self._meta)
            self._ann.__enter__()
        self._t0 = time.perf_counter()

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _, ks, kn = self._keys
        owner = self._owner
        with owner.lock:
            owner.totals[ks] += dt
            owner.totals[kn] += 1
