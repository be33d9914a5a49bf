"""codec_ms.oneshot: wire codec time per request summed over the chain's
hops, ms (``BatchTrace.serialize_s + deserialize_s`` of every stage)."""
from bench import readers


def read(win):
    if win.traffic["kind"] != "oneshot":
        return None
    ns = readers.nodes(win)
    if not ns:
        return None
    return 1e3 * sum(n["serialize_s"] + n["deserialize_s"] for n in ns)
