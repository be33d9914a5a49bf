"""host_copy_ms.decode: host<->device copies of one decode step row along
the chain, ms: over stages, the decode step's ``h2d`` (token and position
upload) and ``d2h`` (outputs to the host: at the tail, the row's logits;
the wait for the step itself included) spans' seconds over the stage's
step rows."""
from bench.metrics.host_copy_ms_oneshot import per_stage


def read(win):
    if win.traffic["kind"] != "decode":
        return None
    return per_stage(win, ("h2d_s", "d2h_s"), "step_rows")
