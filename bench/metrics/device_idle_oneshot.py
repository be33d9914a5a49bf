"""device_idle.oneshot: share of the traced window in which no operation
ran on the device, % (1 - busy / window, from the profiler trace)."""
from bench import readers


def read(win):
    if win.traffic["kind"] != "oneshot":
        return None
    return readers.idle_percent(win)
