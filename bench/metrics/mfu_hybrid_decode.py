"""mfu.hybrid_decode: the whole decode step's share of the chip's peak, %,
for a Mamba2 + attention hybrid: FLOPs of every token yielded in the
window (``bench/work_hybrid.py``: 2 x matmul parameters with the head,
attention over the token's context in the attention layers only, the
recurrence in the Mamba2 layers) / window seconds / peak FLOP/s."""
from bench import readers, work_hybrid


def read(win):
    if win.traffic["kind"] != "decode" or "layer_types" not in win.config:
        return None
    seconds = win.t_end - win.t0
    flops = sum(work_hybrid.decode_flops_per_token(win.config, p)
                for p in readers.token_positions(win))
    return 100.0 * flops / seconds / readers.peak(win).flops_per_s
