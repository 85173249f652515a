"""moonlight-16b-a3b [moe]: the DeepSeek-V3 block at 2,048 wide.

27L d_model=2048 16H, MLA (no q-LoRA, kv_lora_rank 512, qk 128+64,
v 128), one leading dense layer (d_ff 11264), then 26 expert layers of
64 routed experts (1408 wide, top-6, sigmoid scores renormalised and
scaled by 2.446) plus 2 shared experts; vocab 163840, untied.
[hf moonshotai/Moonlight-16B-A3B config.json; arXiv:2412.19437]

Departure: DeepSeek-V3's selection bias (`e_score_correction_bias`) is
held at its initial zero and not updated; its update rule acts outside
the gradient.  Serving (prefill / decode) is not built for MLA.
"""
from repro.configs.base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    arch_id="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    n_dense_layers=1,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=192,
    d_ff=11264,
    vocab_size=163840,
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    rope_theta=50_000.0,
    norm_eps=1e-5,
    moe=MoEConfig(num_experts=64, top_k=6, router="deepseek_v3", d_expert=1408,
                  n_shared=2, routed_scale=2.446, balance_alpha=1e-4),
    source="hf moonshotai/Moonlight-16B-A3B; arXiv:2412.19437",
)
