"""mfu.decode: the whole decode step's share of the chip's peak, %: FLOPs
of every token yielded in the window (``bench/work.py``: 2 x matmul
parameters with the head, plus attention over the token's context) /
window seconds / peak FLOP/s."""
from bench import readers


def read(win):
    if win.traffic["kind"] != "decode":
        return None
    return readers.decode_mfu(win)
