"""queue_wait_ms.decode: one decode row's (one token's) queueing along the
chain, ms: the wait at every in-process hand-off, each over the rows that
waited there (as ``queue_wait_ms.oneshot``)."""
from bench.metrics.queue_wait_ms_oneshot import chain_wait_ms


def read(win):
    if win.traffic["kind"] != "decode":
        return None
    return chain_wait_ms(win)
