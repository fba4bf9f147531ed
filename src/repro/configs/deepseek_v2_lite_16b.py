"""deepseek-v2-lite-16b [mla_moe]: 27L d=2048 16H MLA(kv_lora=512) MoE,
64 routed experts top-6 + 2 shared, vocab 102400.  [arXiv:2405.04434;
hf:deepseek-ai/DeepSeek-V2-Lite config.json]

Routing as published: softmax scores, top-6 weights not renormalised
(``norm_topk_prob`` false, ``routed_scaling_factor`` 1), and the expert
balance loss taken per sequence (``seq_aux``) with coefficient
``AUX_COEF`` (α₁ of arXiv:2405.04434), which a training job passes as
``TrainHParams.aux_coef``.  Rope: YaRN, factor 40 over an original
4096 positions.
"""

from .base import ModelConfig

AUX_COEF = 0.001

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="mla_moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv=16,
    d_ff=1408,                 # per-expert intermediate
    vocab=102_400,
    d_head=128,
    n_experts=64,
    n_shared=2,
    top_k=6,
    norm_topk=False,
    seq_aux=True,
    first_dense=1,
    dense_ff=10_944,
    kv_lora=512,
    q_nope=128,
    q_rope=64,
    v_head=128,
    act="swiglu",
    norm="rmsnorm",
    norm_eps=1e-6,
    rope_theta=10_000.0,
    yarn_factor=40.0,
    yarn_orig_len=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
)

SMOKE_CONFIG = CONFIG.replace(
    n_layers=3, d_model=128, n_heads=4, n_kv=4, d_ff=96, vocab=512,
    d_head=32, n_experts=8, top_k=2, n_shared=1, first_dense=1,
    dense_ff=256, kv_lora=64, q_nope=32, q_rope=16, v_head=32,
    attn_chunk=64, remat=False)
