"""deepseek-v2-lite — latent attention (MLA, YaRN rope), a dense first
layer, then 26 layers of 64 routed experts (top-6, softmax, greedy, not
renormalized, scaled by 1) beside 2 shared experts; untied head
[hf:deepseek-ai/DeepSeek-V2-Lite]."""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
    head_dim=0, d_ff=10944, vocab_size=102400,
    norm_eps=1e-6, rope_theta=10000.0,
    first_k_dense=1,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128, rope_factor=40.0,
                  original_max_position=4096, beta_fast=32.0,
                  beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    # the published seq_aux balance loss has no coefficient in the
    # config; the Switch loss is off, so the loss is the cross-entropy
    moe=MoEConfig(num_experts=64, top_k=6, d_ff_expert=1408,
                  router_aux_coef=0.0, dispatch="dropless",
                  num_shared_experts=2),
    citation="hf:deepseek-ai/DeepSeek-V2-Lite",
)
