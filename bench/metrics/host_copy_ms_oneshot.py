"""host_copy_ms.oneshot: host<->device copies of one request's
activations along the chain, ms: over stages, the ``h2d`` (concatenate,
pad, the upload's call) and ``d2h`` (``np.asarray`` of the outputs, which
waits for the upload, the computation and the copy out) spans' seconds
over the stage's requests.  What device-resident hops remove.

The program's running totals ride each replica's ``per_node`` entry of
the engine's report under ``totals``; a program without them reports
nothing here (``None``)."""
from bench import readers


def totals(win) -> list[dict] | None:
    """The totals of every replica that served anything, with its stage,
    or ``None`` where the program keeps none."""
    ns = readers.nodes(win)
    if not ns or any("totals" not in n for n in ns):
        return None
    return [dict(n["totals"], stage=n["stage"]) for n in ns]


def per_stage(win, seconds: tuple[str, ...], per: str) -> float | None:
    """Sum over stages of the stage's ``seconds`` keys over its ``per``
    key, both summed over the stage's replicas, in ms."""
    reps = totals(win)
    if reps is None:
        return None
    stages: dict[int, list[float]] = {}
    for t in reps:
        s = stages.setdefault(t["stage"], [0.0, 0])
        s[0] += sum(t[k] for k in seconds)
        s[1] += t[per]
    if not any(n for _, n in stages.values()):
        return None
    return 1e3 * sum(s / n for s, n in stages.values() if n)


def read(win):
    if win.traffic["kind"] != "oneshot":
        return None
    return per_stage(win, ("h2d_s", "d2h_s"), "n")
