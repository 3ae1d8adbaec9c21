"""DeepSeek-V2-Lite [hf:deepseek-ai/DeepSeek-V2-Lite, arXiv:2405.04434]:
multi-head latent attention (no query LoRA, latent 512 + roped key 64,
YaRN rope), one leading dense layer, then DeepSeekMoE layers of 64 routed
experts (softmax router, greedy top-6, gates not renormalised, scaling
factor 1) and 2 shared experts.  The published model holds every expert; a chip of an
expert-parallel deployment holds ``n_experts`` of them at
``expert_offset``."""
import dataclasses

from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=10944, vocab_size=102400,
    block_type="llama", norm_type="rmsnorm", tie_embeddings=False,
    rope_theta=10_000.0,
    attention="mla", kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128,
    yarn_factor=40.0, yarn_original_max_pos=4096, yarn_beta_fast=32.0,
    yarn_beta_slow=1.0, yarn_mscale=0.707,
    n_experts=64, n_routed_experts=64, top_k=6, moe_d_ff=1408,
    n_shared_experts=2, norm_topk_prob=False, moe_dropless=True,
    n_dense_layers=1,
)


def tiny() -> ModelConfig:
    """Every kind of layer at a small width; half the router's experts
    held, as on a chip of a two-way expert-parallel deployment."""
    return dataclasses.replace(
        CONFIG, name="deepseek-v2-lite-tiny", n_layers=3, d_model=64,
        n_heads=4, n_kv_heads=4, d_ff=96, vocab_size=256, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
        n_experts=4, n_routed_experts=8, top_k=3, moe_d_ff=16)
