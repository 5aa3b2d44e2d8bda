"""ArchConfig — one frozen dataclass describing every supported family.

Families:
    dense   — GQA decoder transformer (mistral-nemo, qwen3, granite, qwen2)
    moe     — dense attention (or MLA) + mixture-of-experts MLP
    ssm     — RWKV-6 (attention-free)
    hybrid  — Mamba-2 backbone + shared attention block (zamba2)
    vlm     — dense backbone + M-RoPE + stubbed patch-embedding frontend
    audio   — dense backbone over EnCodec codebook tokens (stub frontend)
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str
    n_layers: int
    d_model: int
    n_heads: int
    d_ff: int
    vocab: int
    n_kv_heads: int = 0             # 0 → = n_heads
    d_head: int = 0                 # 0 → d_model // n_heads
    # attention details
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 1e6
    mrope_sections: Optional[Tuple[int, ...]] = None   # qwen2-vl
    # MoE
    n_experts: int = 0
    n_experts_active: int = 0
    n_shared_experts: int = 0
    moe_group_size: int = 256
    # capacity factor: tokens over C = group·k/E·cf are dropped.  NOTE:
    # capacity competition makes MoE outputs depend on group composition,
    # so prefill-vs-decode parity is only exact with cf high enough to
    # never drop (tests use cf ≥ E/k).
    moe_capacity_factor: float = 1.25
    # dropless expert layer (DeepSeek-V3): every routed token reaches its
    # experts; the layer holds experts [expert_first, expert_first +
    # experts_held) of n_experts (0: all) and computes their part only
    moe_dropless: bool = False
    expert_first: int = 0
    experts_held: int = 0
    # routing: softmax top-k, or DeepSeek-V3's sigmoid scores with
    # group-limited top-k (n_groups groups, the topk_groups best kept)
    router_score: str = "softmax"    # softmax | sigmoid
    n_groups: int = 1
    topk_groups: int = 1
    routed_scale: float = 1.0
    # leading dense layers before the MoE layers (first_k_dense_replace)
    n_dense_layers: int = 0
    d_ff_dense: int = 0             # their MLP width; 0 → d_ff
    # YaRN rope scaling (DeepSeek-V3); factor 0 → plain rope
    yarn_factor: float = 0.0
    yarn_original_max_pos: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    # MLA (deepseek-v3)
    use_mla: bool = False
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # SSM / hybrid
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    attn_every: int = 0             # zamba2: shared attn after every k mamba
    # audio
    n_codebooks: int = 0            # musicgen EnCodec codebooks
    # numerics / execution
    dtype: str = "bfloat16"
    norm_eps: float = 1e-5
    attn_q_block: int = 512
    attn_kv_block: int = 512
    attn_impl: str = "masked"       # masked | balanced
    la_chunk: int = 32              # linear-attention chunk length
    fsdp: bool = False              # shard weights on the DP axis too
    seq_parallel: bool = False      # Megatron-SP residual sharding
    scan_layers: bool = True
    # embedding tying
    tie_embeddings: bool = False

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def n_held(self) -> int:
        """Experts this layer holds (the chip's share)."""
        return self.experts_held or self.n_experts

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def jdtype(self):
        import jax.numpy as jnp
        return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
                "float16": jnp.float16}[self.dtype]

    def replace(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)
