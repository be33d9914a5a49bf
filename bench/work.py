"""The work an algorithm needs, counted from a configuration's shapes.

These functions read only the configuration files' sizes, never the
program's own cost fields, so a change to the program cannot change what
a share of a peak or of a roofline is measured against.  A FLOP is one
multiply or one add (a multiply-add is two).
"""
from __future__ import annotations

from typing import Iterable

F32_BYTES = 4


def resnet_flops_per_image(cfg: dict) -> float:
    """Convolutions and the classifier of one image: 2 x multiply-adds.
    Batch-norm affines, ReLUs, residual adds and pools are left out (under
    0.1% of the total)."""
    size = cfg["image_size"]
    k = cfg["stem_kernel"]
    h = -(-size // 2)                                   # stem, stride 2
    macs = h * h * k * k * cfg["in_channels"] * cfg["stem_width"]
    h = -(-h // 2)                                      # max pool, stride 2
    cin = cfg["stem_width"]
    for si, (blocks, mid) in enumerate(zip(cfg["stage_blocks"],
                                           cfg["bottleneck_widths"])):
        cout = mid * cfg["expansion"]
        for bi in range(blocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            ho = -(-h // stride)
            macs += h * h * cin * mid                   # 1x1 reduce
            macs += ho * ho * 9 * mid * mid             # 3x3 (carries stride)
            macs += ho * ho * mid * cout                # 1x1 expand
            if bi == 0:
                macs += ho * ho * cin * cout            # projection shortcut
            cin, h = cout, ho
    macs += cin * cfg["num_classes"]
    return 2.0 * macs


def lm_matmul_params(cfg: dict) -> int:
    """Weights a decoder multiplies by per token: attention projections,
    MLP and the output head (the embedding is a gather, not a matmul)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    per_layer = d * q + 2 * d * kv + q * d + 2 * d * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def attention_flops(cfg: dict, positions: int) -> float:
    """One query token against ``positions`` keys, one layer: QK^T and PV."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * positions


def decode_flops_per_token(cfg: dict, pos: int) -> float:
    """FLOPs to decode the token at position ``pos`` (it attends over
    ``pos + 1`` positions, itself included) through every layer."""
    return (2.0 * lm_matmul_params(cfg)
            + cfg["num_hidden_layers"] * attention_flops(cfg, pos + 1))


def decode_attention_work(cfg: dict, positions: Iterable[int]
                          ) -> tuple[float, float]:
    """(FLOPs, bytes) that one layer's decode attention needs for rows at
    ``positions``: each row reads K and V for its ``pos + 1`` cached
    positions, reads its query and writes its output, all in f32.  Work on
    cache slots past ``pos`` (which a kernel may stream and mask) is not
    needed work and is not counted."""
    kv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    q_bytes = cfg["num_attention_heads"] * hd * F32_BYTES
    flops = nbytes = 0.0
    for pos in positions:
        n = pos + 1
        flops += attention_flops(cfg, n)
        nbytes += 2 * n * kv * hd * F32_BYTES + 2 * q_bytes
    return flops, nbytes
