"""stage_compute_ms.decode: the busiest stage's compute time per decode
wave, ms (sum of ``BatchTrace.compute_s`` over its waves / waves)."""
from bench import readers


def read(win):
    if win.traffic["kind"] != "decode":
        return None
    node = readers.busiest(win)
    return None if node is None else 1e3 * node["compute_s"] * node["batch_mean"]
