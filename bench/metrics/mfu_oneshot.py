"""mfu.oneshot: the whole model step's share of the chip's peak, %:
ResNet FLOPs per image (``bench/work.py``, from the configuration's
shapes) x requests completed per second in the window / peak FLOP/s."""
from bench import readers, work


def read(win):
    if win.traffic["kind"] != "oneshot":
        return None
    done = sum(1 for r in win.records
               if r.error is None and r.t_done <= win.t_end)
    rate = done / (win.t_end - win.t0)
    return (100.0 * rate * work.resnet_flops_per_image(win.config)
            / readers.peak(win).flops_per_s)
