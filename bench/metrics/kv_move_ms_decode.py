"""kv_move_ms.decode: the busiest replica's KV cache traffic per decode
wave, ms: host time of its ``kv_gather`` (the wave's per-session caches
concatenated) and ``kv_scatter`` (sliced back, one ``SessionStore.put``
per session) spans over its waves.  What a slot-indexed cache removes."""
from bench import readers


def read(win):
    if win.traffic["kind"] != "decode":
        return None
    node = readers.busiest(win)
    t = node.get("totals") if node else None
    if not t or not t["waves"]:
        return None
    return 1e3 * (t["kv_gather_s"] + t["kv_scatter_s"]) / t["waves"]
