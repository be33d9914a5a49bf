"""A decoder-only transformer (``models/lm_graph.py``) as the program
builds it, at a configuration's widths."""
from __future__ import annotations

KIND = "decode"


def build_graph(cfg: dict):
    from repro.models.lm_graph import decode_lm_graph

    return decode_lm_graph(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"],
        cache_len=cfg["max_position_embeddings"],
        use_kernel=cfg["serving"]["use_kernel"])
