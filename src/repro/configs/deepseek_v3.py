"""DeepSeek-V3 at its published math — the architecture DeepSeek-R1 shares.

[arXiv:2412.19437, config.json of deepseek-ai/DeepSeek-V3] 61 layers: 3
dense of FFN width 18432, then experts of width 2048 behind a sigmoid router
with a selection bias (``noaux_tc``: 8 groups, the best 4 kept, top-8,
renormalised, scaled by 2.5), one shared expert; MLA; YaRN rope (factor 40
over 4096 positions, beta 32/1, mscale 1/1). The MTP layer is not part of
this config. ``deepseek-r1`` is the paper's older proxy of the same shapes
(softmax router, one FFN width, plain rope).
"""
from repro.configs.base import DeepSeekV3Config, register


@register
def deepseek_v3() -> DeepSeekV3Config:
    return DeepSeekV3Config(
        name="deepseek-v3",
        family="moe",
        source="arXiv:2412.19437; huggingface.co/deepseek-ai/DeepSeek-V3",
        num_layers=61,
        d_model=7168,
        num_heads=128,
        num_kv_heads=128,        # MLA: latent cache shared; heads expanded on the fly
        head_dim=192,            # qk_nope(128) + qk_rope(64)
        d_ff=18432,              # dense layers' FFN width
        moe_d_ff=2048,           # routed and shared experts' width
        vocab_size=129280,
        attention_kind="mla",
        q_lora_rank=1536,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        num_experts=256,
        num_experts_per_tok=8,
        num_shared_experts=1,
        first_k_dense=3,
        router_score="sigmoid",
        n_group=8,
        topk_group=4,
        routed_scaling=2.5,
        rope_theta=10_000.0,
        yarn_factor=40.0,
        yarn_original_max=4096,
        yarn_beta_fast=32.0,
        yarn_beta_slow=1.0,
        yarn_mscale=1.0,
        yarn_mscale_all_dim=1.0,
        norm_eps=1e-6,
        sliding_window=8192,
    )
