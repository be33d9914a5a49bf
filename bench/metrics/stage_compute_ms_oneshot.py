"""stage_compute_ms.oneshot: the busiest stage's compute time per request
it served, ms (``BatchTrace.compute_s``: host time around the jitted apply,
ending in the copy of its output to the host)."""
from bench import readers


def read(win):
    if win.traffic["kind"] != "oneshot":
        return None
    node = readers.busiest(win)
    return None if node is None else 1e3 * node["compute_s"]
