"""resident_hop_share.decode: the share of the envelopes (session opens,
step rows, closes) the non-tail stages handed downstream that stayed on
the device, %: as ``resident_hop_share.oneshot``."""
from bench.metrics.resident_hop_share_oneshot import share


def read(win):
    if win.traffic["kind"] != "decode":
        return None
    return share(win)
