"""The program's DeepSeek-V3 chip share (MLA, leading dense layers, the
dropless expert layer over the held experts), built from a configuration
file."""
from __future__ import annotations

from repro.configs import get_config


def arch_config(c: dict):
    """The program's ArchConfig, every size taken from the file."""
    assert c["scoring_func"] == "sigmoid" and c["topk_method"] == "noaux_tc"
    assert c["norm_topk_prob"] and c["hidden_act"] == "silu"
    assert c["moe_layer_freq"] == 1 and c["num_nextn_predict_layers"] == 0
    y = c["rope_scaling"]
    assert y["type"] == "yarn"
    return get_config(c["registry"]).replace(
        n_layers=c["num_hidden_layers"],
        n_dense_layers=c["first_k_dense_replace"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], d_ff=c["moe_intermediate_size"],
        d_ff_dense=c["intermediate_size"], vocab=c["vocab_held"],
        n_experts=c["n_routed_experts_published"],
        expert_first=c["first_held_expert"],
        experts_held=c["n_routed_experts"],
        n_experts_active=c["num_experts_per_tok"],
        n_shared_experts=c["n_shared_experts"], n_groups=c["n_group"],
        topk_groups=c["topk_group"],
        routed_scale=float(c["routed_scaling_factor"]),
        q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        rope_theta=float(c["rope_theta"]), yarn_factor=float(y["factor"]),
        yarn_original_max_pos=y["original_max_position_embeddings"],
        yarn_beta_fast=float(y["beta_fast"]),
        yarn_beta_slow=float(y["beta_slow"]), yarn_mscale=float(y["mscale"]),
        yarn_mscale_all_dim=float(y["mscale_all_dim"]),
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]), dtype=c["torch_dtype"],
        attn_impl="balanced", fsdp=False)
