"""deepseek-v2-lite-16b — MLA + fine-grained MoE [arXiv:2405.04434].

As published (https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite,
config.json): 27 layers, the first with a dense SwiGLU of width 10944
(``first_k_dense_replace`` 1), the rest 64 routed experts of width 1408
(greedy top-6 of a softmax, weights not renormalised) beside 2 shared
ones; MLA with no query LoRA, ``kv_lora_rank`` 512 with its RMSNorm,
rope dim 64 under YaRN (factor 40 over an original 4096 positions);
RMSNorm eps 1e-6, untied head.
"""

from repro.configs.base import ModelConfig, Yarn

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,  # MLA: per-head latents, kv head count unused
    d_ff=10944,
    vocab_size=102400,
    mla=True,
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    moe=True,
    n_routed_experts=64,
    n_shared_experts=2,
    top_k=6,
    moe_d_ff=1408,
    first_dense_layers=1,
    norm_topk_prob=False,
    rope_theta=10000.0,
    rope_scaling=Yarn(factor=40.0, original_max_position=4096,
                      beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                      mscale_all_dim=0.707),
    norm_eps=1e-6,
    max_position=163840,
    source="arXiv:2405.04434 (DeepSeek-V2-Lite)",
)
