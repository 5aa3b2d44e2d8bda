"""deepseek-v3-671b — DeepSeek-V3 [arXiv:2412.19437; hf config.json].

61 layers, d_model 7168, 128-head MLA (q_lora 1536, kv_lora 512, nope
128, rope 64, v 128) with YaRN rope (factor 40 over 4096 positions,
β_fast 32, β_slow 1, mscale 1, mscale_all_dim 1, θ 1e4); the first 3
layers dense (d_ff 18432), the other 58 MoE: 1 shared + 256 routed
experts of width 2048, top-8.  Routing is the published ``noaux_tc``
rule: sigmoid scores, 8 groups ranked by the sum of each group's top-2
biased scores, the best 4 kept, top-8 experts among them, weights the
chosen experts' unbiased scores normalized and ×2.5
(``models.moe.route``); the score-correction bias is a zero buffer, not
trained.  Dropless: no routed token is dropped.  Vocabulary 129280,
untied; RMSNorm eps 1e-6.  The multi-token-prediction module is not
built.
"""
from repro.models.config import ArchConfig


def config() -> ArchConfig:
    return ArchConfig(
        name="deepseek-v3-671b",
        family="moe",
        n_layers=61,
        d_model=7168,
        n_heads=128,
        d_ff=2048,                 # routed-expert inner dim
        vocab=129280,
        n_experts=256,
        n_experts_active=8,
        n_shared_experts=1,
        moe_group_size=128,
        moe_dropless=True,
        router_score="sigmoid",
        n_groups=8,
        topk_groups=4,
        routed_scale=2.5,
        n_dense_layers=3,
        d_ff_dense=18432,
        use_mla=True,
        q_lora_rank=1536,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        rope_theta=1e4,
        yarn_factor=40.0,
        yarn_original_max_pos=4096,
        yarn_beta_fast=32.0,
        yarn_beta_slow=1.0,
        yarn_mscale=1.0,
        yarn_mscale_all_dim=1.0,
        norm_eps=1e-6,
        fsdp=True,
    )


def smoke_config() -> ArchConfig:
    """The same rules at a tiny size: 1 dense + 1 MoE layer, 8 experts in
    4 groups (2 kept), top-2, YaRN over 16 original positions."""
    return config().replace(
        n_layers=2, n_dense_layers=1, d_model=64, n_heads=4, d_ff=64,
        d_ff_dense=96, vocab=128,
        n_experts=8, n_experts_active=2, n_shared_experts=1,
        n_groups=4, topk_groups=2, moe_group_size=32,
        q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, yarn_factor=4.0,
        yarn_original_max_pos=16, dtype="float32", fsdp=False,
        attn_q_block=16, attn_kv_block=16,
    )
