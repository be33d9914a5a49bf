"""decode_attention_roofline: the ``decode_attention`` kernel's share of its
roofline, %.  The least time the chip could take for the work the
algorithm needs (``bench/work.py``: each row's ``pos + 1`` cached
positions, one call per attention layer per decode step whose token
arrived inside the traced window) over the kernel's device time in the
trace, with the time of the copies that stage its KV operands into the
kernel's layout and back (XLA may place them in VMEM, so the kernel's own
events leave out reading them from HBM)."""
from bench import readers, work
from bench.peaks import roofline_share

KERNEL = "decode_attention"


def read(win):
    if win.traffic["kind"] != "decode" or win.trace is None:
        return None
    seconds = (win.trace.kernel_s.get(KERNEL, 0.0)
               + win.trace.staging_s.get(KERNEL, 0.0))
    positions = readers.step_positions(win, *win.trace_t)
    if not seconds or not positions:
        return None
    flops, nbytes = work.decode_attention_work(win.config, positions)
    layers = win.config["num_hidden_layers"]
    share, _ = roofline_share(layers * flops, layers * nbytes, seconds,
                              readers.peak(win))
    return share
