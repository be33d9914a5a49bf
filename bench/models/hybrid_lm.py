"""A Mamba2 + attention hybrid decoder (``models/lm_graph.py``, Granite-4.0-H
form) as the program builds it, at a configuration's widths: the first
``num_hidden_layers`` of ``layer_types``, no position embedding, the
gated SiLU MLP and the embedding, residual and logit multipliers."""
from __future__ import annotations

KIND = "decode"


def build_graph(cfg: dict):
    from repro.configs.base import SSMConfig
    from repro.models.lm_graph import decode_lm_graph

    d, n_layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    if (cfg["mamba_n_groups"] != 1 or cfg["num_local_experts"] != 0
            or cfg["rms_norm_eps"] != 1e-5):
        raise ValueError("the program's Mamba2 has one group, its MLP no "
                         "experts and its RMSNorm eps 1e-5")
    ssm = SSMConfig(state_dim=cfg["mamba_d_state"],
                    head_dim=cfg["mamba_d_head"],
                    expand=cfg["mamba_expand"],
                    chunk=cfg["mamba_chunk_size"],
                    conv_width=cfg["mamba_d_conv"])
    if cfg["mamba_expand"] * d != cfg["mamba_n_heads"] * cfg["mamba_d_head"]:
        raise ValueError("mamba_n_heads x mamba_d_head must be the inner width")
    return decode_lm_graph(
        vocab=cfg["vocab_size"], d_model=d, n_layers=n_layers,
        num_heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["shared_intermediate_size"],
        cache_len=cfg["max_position_embeddings"],
        use_kernel=cfg["serving"]["use_kernel"],
        layer_types=cfg["layer_types"][:n_layers], mlp_kind="gated_silu",
        rope=cfg["position_embedding_type"] != "nope",
        attn_scale=cfg["attention_multiplier"],
        embed_scale=cfg["embedding_multiplier"],
        residual_scale=cfg["residual_multiplier"],
        logits_scaling=cfg["logits_scaling"], ssm=ssm)
