"""The work a Mamba2 + attention hybrid decoder needs, counted from a
configuration's sizes (``bench/configs/granite-4.0-h-micro.json``'s keys)
only, never from the program's own cost fields, as ``bench/work.py`` does
for the plain decoder.  A FLOP is one multiply or one add."""
from __future__ import annotations

F32_BYTES = 4


def layer_types(cfg: dict) -> list[str]:
    """The kinds of the configuration's layers: the first
    ``num_hidden_layers`` of ``layer_types``."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def _mamba_sizes(cfg: dict) -> tuple[int, int, int, int]:
    """(heads H, head size P, state size N, groups G) of a Mamba2 layer."""
    return (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
            cfg["mamba_n_groups"])


def matmul_params(cfg: dict) -> int:
    """Weights a token is multiplied by: each Mamba2 layer's in and out
    projections, each attention layer's Q, K, V and O projections, each
    layer's gated MLP (``W_in`` of twice the MLP width, ``W_out``) and the
    output head.  The embedding is a gather and the depthwise conv (4
    taps a channel) is left out (under 0.1% of the total)."""
    d = cfg["hidden_size"]
    h, p, n, g = _mamba_sizes(cfg)
    di = h * p
    mamba = d * (2 * di + 2 * g * n + h) + di * d
    hd = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    attention = d * q + 2 * d * kv + q * d
    mlp = 3 * d * cfg["shared_intermediate_size"]
    types = layer_types(cfg)
    return (types.count("mamba") * mamba + types.count("attention") * attention
            + len(types) * mlp + d * cfg["vocab_size"])


def ssm_step_flops(cfg: dict) -> float:
    """One token through one Mamba2 layer's recurrence: per head the decay
    (``P N`` multiplies), the rank-1 update (``P N`` multiply-adds) and the
    readout ``S C`` (``P N`` multiply-adds)."""
    h, p, n, _ = _mamba_sizes(cfg)
    return 5.0 * h * p * n


def ssm_step_bytes(cfg: dict) -> float:
    """What one token's recurrence in one Mamba2 layer must move: the f32
    state read and written, and its inputs ``x``, ``dt``, ``B``, ``C`` and
    output ``y``."""
    h, p, n, g = _mamba_sizes(cfg)
    state = h * p * n
    return F32_BYTES * (2 * state + 2 * h * p + h + 2 * g * n)


def decode_flops_per_token(cfg: dict, pos: int) -> float:
    """FLOPs to decode the token at position ``pos``: the matmuls, each
    attention layer over ``pos + 1`` positions (QK^T and PV), and each
    Mamba2 layer's recurrence."""
    types = layer_types(cfg)
    attention = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * (pos + 1)
    return (2.0 * matmul_params(cfg) + types.count("attention") * attention
            + types.count("mamba") * ssm_step_flops(cfg))
