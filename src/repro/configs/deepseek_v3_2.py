"""deepseek-v3.2 [moe, MLA, DSA]: 61L d7168 128H, multi-head latent
attention with compressed queries (q_lora_rank 1536, kv_lora_rank 512,
qk_nope 128 + qk_rope 64, v_head 128) and DeepSeek Sparse Attention (a
lightning indexer of 64 heads x 128 over an FP8 key cache picks the top
2048 cached positions per query); layers 0-2 dense (MLP 18432), layers
3-60 DeepSeekMoE: 256 routed experts of width 2048, 8 per token, 1 shared
expert; vocab 129280, context 163840; one multi-token prediction module.
[hf:deepseek-ai/DeepSeek-V3.2 config.json; arXiv:2405.04434 Sec. 2.1
(MLA), 2.2 (DeepSeekMoE); the DeepSeek-V3.2-Exp report (DSA)]

``router_skew`` and ``router_seed`` are assumed, not published: they fix
the synthetic routing that the PPA lowering draws for one decode step
(:func:`repro.core.workloads.from_config`), in place of the published
sigmoid grouped top-k (8 groups, 4 of them per token).
"""
from repro.configs.base import ModelConfig, register


@register("deepseek-v3.2")
def deepseek_v3_2() -> ModelConfig:
  return ModelConfig(
      name="deepseek-v3.2", family="moe",
      n_layers=61, d_model=7168, n_heads=128, n_kv_heads=128, head_dim=128,
      d_ff=18432, vocab_size=129280,
      mlp_variant="swiglu", norm="rmsnorm", pos_embed="rope",
      max_position=163840,
      kv_lora_rank=512, q_lora_rank=1536, qk_rope_head_dim=64,
      v_head_dim=128,
      index_topk=2048, index_n_heads=64, index_head_dim=128,
      n_experts=256, n_experts_active=8, n_shared_experts=1,
      d_ff_expert=2048, d_ff_shared=2048, first_dense_layers=3,
      router_skew=0.5, router_seed=2412,
      source="hf:deepseek-ai/DeepSeek-V3.2",
  )
