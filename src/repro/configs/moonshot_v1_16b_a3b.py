"""moonshot-v1-16b-a3b [moe] — Moonlight-16B-A3B (DeepSeek-V3 block):
27L d_model=2048, dense layer 0 (SwiGLU width 11264), then 26 MoE layers of
64 routed experts (width 1408, top-6) + 2 shared; sigmoid router with a
selection-only correction bias (noaux_tc, one group), renormalised weights
x 2.446; latent attention with 16 heads: no query LoRA, kv_lora_rank 512,
qk_nope 128 + qk_rope 64, v 128; rope_theta 50000; vocab 163840.
[hf:moonshotai/Moonlight-16B-A3B config.json]"""
from repro.models.lm import LMConfig


def config() -> LMConfig:
    return LMConfig(
        name="moonshot-v1-16b-a3b", family="moe",
        n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, d_head=192,
        d_ff=11264, vocab=163840, mlp_type="swiglu", rope_theta=50000.0,
        norm_eps=1e-5, n_experts=64, top_k=6, n_shared=2, d_expert=1408,
        first_dense_ff=11264, router_score="sigmoid", routed_scaling=2.446,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, moe_dropless_prefill=True,
    )


def smoke_config() -> LMConfig:
    return LMConfig(
        name="moonshot-v1-16b-a3b-smoke", family="moe",
        n_layers=3, d_model=128, n_heads=4, n_kv_heads=4, d_head=48,
        d_ff=384, vocab=512, mlp_type="swiglu", rope_theta=50000.0,
        norm_eps=1e-5, n_experts=8, top_k=2, n_shared=1, d_expert=96,
        first_dense_ff=384, router_score="sigmoid", routed_scaling=2.446,
        kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
        v_head_dim=32, moe_group_size=64, remat="none",
        moe_dropless_prefill=True,
    )
