"""DeepSeek-V2-Lite [hf:deepseek-ai/DeepSeek-V2-Lite, arXiv:2405.04434].

27L d_model=2048, vocab 102400 (untied), rms_norm_eps 1e-6. Multi-head
latent attention: 16 heads, kv_lora_rank 512, no query compression,
qk_nope 128 + qk_rope 64, v 128; YaRN rotary scaling (factor 40 over an
original 4096 positions, beta_fast 32, beta_slow 1, mscale = mscale_all_dim
= 0.707). One leading dense SwiGLU layer of width 10944, then 26 layers of
64 routed experts of width 1408 (softmax scores, greedy top-6, no
renormalisation, scaling 1), dropless, plus 2 shared experts; the
sequence-level balance loss at aux_loss_alpha 0.001.
"""
from repro.models.config import MLAConfig, ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,
    vocab=102400,
    block_pattern=("mla+moe",),
    n_dense_lead=1,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64, v_dim=128),
    yarn=YarnConfig(factor=40.0, original_max_pos=4096, beta_fast=32.0,
                    beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    moe=MoEConfig(n_experts=64, top_k=6, dispatch="dropless", d_expert=1408,
                  n_shared=2, aux_alpha=0.001),
    activation="swiglu",
    rope_theta=10000.0,
    norm_eps=1e-6,
    tie_embeddings=False,
)
