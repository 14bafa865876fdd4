"""deepseek-v2-lite-16b [moe] — MLA kv_lora=512, 64 routed top-6 +
2 shared experts, first layer dense (arXiv:2405.04434;
hf:deepseek-ai/DeepSeek-V2-Lite config.json).  The assignment line's "160
routed" is the full-V2 config; V2-Lite has 64 (DESIGN §8).

Routing is the published one (``norm_topk_prob`` false,
``routed_scaling_factor`` 1): the top 6 of a softmax over all 64
experts, not renormalised.  The MLA rope head uses the published YaRN
scaling (factor 40 over an original 4,096 positions, beta_fast 32,
beta_slow 1, mscale = mscale_all_dim = 0.707), which also scales the
softmax.  The benchmark's configuration (``bench/configs/
deepseek-v2-lite.json``) runs these widths cut to 9 of the 27 layers
(dense layer 0 and 8 MoE layers: one of three pipeline stages)."""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, head_dim=192,
    d_ff=10_944, vocab_size=102_400,
    mla=True, kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
    v_head_dim=128,
    n_experts=64, n_shared_experts=2, top_k=6, moe_d_ff=1408,
    first_dense_layers=1, norm_topk_prob=False,
    rope_yarn_factor=40.0, rope_yarn_original_max=4096,
    rope_yarn_beta_fast=32.0, rope_yarn_beta_slow=1.0,
    rope_yarn_mscale=0.707, rope_yarn_mscale_all_dim=0.707,
)
