"""DeepSeek-V2-Lite: multi-head latent attention (16 heads, kv_lora_rank
512, no q_lora, YaRN RoPE) in all 27 layers; a dense first layer
(d_ff=10944), then DeepSeekMoE layers of 64 routed experts of width 1408,
softmax top-6 without renormalisation, plus 2 shared experts
[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite]."""
import jax.numpy as jnp
from ..models.config import BlockSpec, ModelConfig, YaRN


def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite", arch_type="moe", source="arXiv:2405.04434",
        num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
        d_ff=10944, moe_d_ff=1408, vocab_size=102400,
        prefix_blocks=(BlockSpec("attn", "swiglu"),),
        block_pattern=(BlockSpec("attn", "moe"),),
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128,
        yarn=YaRN(factor=40.0, original_max_position=4096, beta_fast=32.0,
                  beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
        num_experts=64, num_experts_per_tok=6, num_shared_experts=2,
        norm_topk_prob=False, moe_impl="gmm",
        norm="rmsnorm", norm_eps=1e-6, rope="rope",
    ).validate()


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-smoke", arch_type="moe", source="arXiv:2405.04434",
        num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
        d_ff=128, moe_d_ff=32, vocab_size=512,
        prefix_blocks=(BlockSpec("attn", "swiglu"),),
        block_pattern=(BlockSpec("attn", "moe"),),
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16,
        yarn=YaRN(factor=40.0, original_max_position=64, beta_fast=32.0,
                  beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
        num_experts=4, num_experts_per_tok=2, num_shared_experts=1,
        norm_topk_prob=False, moe_impl="gmm",
        norm="rmsnorm", norm_eps=1e-6, rope="rope",
        param_dtype=jnp.float32, compute_dtype=jnp.float32,
    ).validate()
