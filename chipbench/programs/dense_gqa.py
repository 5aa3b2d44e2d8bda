"""The program's dense GQA decoder (Qwen3, Mistral-Nemo), built from a
configuration file."""
from __future__ import annotations

from repro.configs import get_config


def arch_config(c: dict):
    """The program's ArchConfig, every size taken from the file."""
    return get_config(c["registry"]).replace(
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        d_head=c["head_dim"], d_ff=c["intermediate_size"],
        vocab=c["vocab_size"], norm_eps=float(c["rms_norm_eps"]),
        rope_theta=float(c["rope_theta"]), qk_norm=c["model_type"] == "qwen3",
        qkv_bias=bool(c.get("attention_bias", False)),
        tie_embeddings=bool(c["tie_word_embeddings"]), dtype=c["torch_dtype"])
