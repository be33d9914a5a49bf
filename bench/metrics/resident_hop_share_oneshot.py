"""resident_hop_share.oneshot: the share of the envelopes the non-tail
stages handed downstream that stayed on the device, %: 100 x the
counters ``resident`` / (``resident`` + ``encodes``) summed over the
replicas of every stage but the last.  Nothing to read from a program
without the ``resident`` counter."""
from bench.metrics.host_copy_ms_oneshot import totals


def share(win) -> float | None:
    reps = totals(win)
    if not reps or any("resident" not in t for t in reps):
        return None
    tail = max(t["stage"] for t in reps)
    inner = [t for t in reps if t["stage"] < tail]
    resident = sum(t["resident"] for t in inner)
    handed = resident + sum(t["encodes"] for t in inner)
    return 100.0 * resident / handed if handed else None


def read(win):
    if win.traffic["kind"] != "oneshot":
        return None
    return share(win)
