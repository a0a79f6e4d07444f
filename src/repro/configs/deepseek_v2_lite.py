"""deepseek-v2-lite [moe, MLA]: 27L d2048 16H, multi-head latent
attention (kv_lora_rank 512, no query compression, qk_nope 128 + qk_rope
64, v_head 128), layer 0 dense (MLP 10944), layers 1-26 DeepSeekMoE: 64
routed experts of width 1408, 6 per token, 2 shared experts; vocab
102400, context 163840.
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434 Sec. 2.1
(MLA), 2.2 (DeepSeekMoE)]

``router_skew`` and ``router_seed`` are assumed, not published: they fix
the synthetic routing that the PPA lowering draws for one decode step
(:func:`repro.core.workloads.from_config`).
"""
from repro.configs.base import ModelConfig, register


@register("deepseek-v2-lite")
def deepseek_v2_lite() -> ModelConfig:
  return ModelConfig(
      name="deepseek-v2-lite", family="moe",
      n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, head_dim=128,
      d_ff=10944, vocab_size=102400,
      mlp_variant="swiglu", norm="rmsnorm", pos_embed="rope",
      max_position=163840,
      kv_lora_rank=512, q_lora_rank=0, qk_rope_head_dim=64, v_head_dim=128,
      n_experts=64, n_experts_active=6, n_shared_experts=2,
      d_ff_expert=1408, d_ff_shared=2816, first_dense_layers=1,
      router_skew=0.5, router_seed=2405,
      source="hf:deepseek-ai/DeepSeek-V2-Lite",
  )
