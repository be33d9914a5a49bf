"""wave_rows.decode: mean session frames per decode wave (``BatchTrace.n``)
over both stages in the window (engine and dispatcher)."""
from bench import readers


def read(win):
    if win.traffic["kind"] != "decode":
        return None
    return readers.mean_rows(win)
