"""ssm_step_roofline: the ``ssm_step`` kernel's share of its roofline, %.
The least time the chip could take for the work one Mamba2 decode step
needs (``bench/work_hybrid.py``: the f32 state read and written and the
step's inputs and output, per real row, one call per Mamba2 layer per
decode step whose token arrived inside the traced window) over the
kernel's device time in the trace.  Padding rows of a wave are not needed
work.  Nothing to read where no ``ssm_step`` ran (a program without the
kernel, or a configuration without Mamba2 layers)."""
from bench import readers, work_hybrid
from bench.peaks import roofline_share

KERNEL = "ssm_step"


def read(win):
    if win.traffic["kind"] != "decode" or win.trace is None:
        return None
    if "layer_types" not in win.config:
        return None
    seconds = win.trace.kernel_s.get(KERNEL, 0.0)
    rows = len(readers.step_positions(win, *win.trace_t))
    layers = work_hybrid.layer_types(win.config).count("mamba")
    if not seconds or not rows or not layers:
        return None
    calls = rows * layers
    share, _ = roofline_share(calls * work_hybrid.ssm_step_flops(win.config),
                              calls * work_hybrid.ssm_step_bytes(win.config),
                              seconds, readers.peak(win))
    return share
