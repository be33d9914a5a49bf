"""batch_rows.oneshot: mean requests per merged batch (``BatchTrace.n``)
over every stage of the chain in the window (engine and dispatcher)."""
from bench import readers


def read(win):
    if win.traffic["kind"] != "oneshot":
        return None
    return readers.mean_rows(win)
