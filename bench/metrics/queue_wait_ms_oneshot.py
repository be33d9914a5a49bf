"""queue_wait_ms.oneshot: one request's queueing along the chain, ms: the
wait at every in-process hand-off (the dispatcher's admission, router
input and result queues; each stage's inbox, compute and egress queues,
its replicas summed), each over the requests that waited there.  Program
counters, stamped at enqueue and read at dequeue; the dispatcher's ride
``EngineReport.dispatcher``."""
from bench.metrics.host_copy_ms_oneshot import totals


def chain_wait_ms(win) -> float | None:
    reps = totals(win)
    disp = getattr(win.report, "dispatcher", None)
    if reps is None or not disp:
        return None
    waits: dict[tuple, list[float]] = {}

    def fold(owner, t: dict) -> None:
        for k, v in t.items():
            if k.startswith("wait_") and k.endswith("_s"):
                w = waits.setdefault((owner, k), [0.0, 0])
                w[0] += v
                w[1] += t[k[:-1] + "n"]

    fold("dispatcher", disp)
    for t in reps:
        fold(t["stage"], t)
    if not any(n for _, n in waits.values()):
        return None
    return 1e3 * sum(s / n for s, n in waits.values() if n)


def read(win):
    if win.traffic["kind"] != "oneshot":
        return None
    return chain_wait_ms(win)
