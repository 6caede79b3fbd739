"""moonshot-v1-16b-a3b (Moonlight-16B-A3B) — DeepSeek-V3 blocks.

Catalog row [hf:moonshotai/Moonlight-16B-A3B config.json, model_type
deepseek_v3]: 27 layers at hidden 2048, 16 heads; the first layer
dense (``first_k_dense_replace`` 1, ``intermediate_size`` 11264), the
other 26 MoE with 64 routed experts of width 1408, 6 per token, 2
shared experts; sigmoid scores with ``noaux_tc`` selection
(``n_group`` = ``topk_group`` = 1, ``norm_topk_prob``,
``routed_scaling_factor`` 2.446); latent attention with no q-LoRA,
``kv_lora_rank`` 512, ``qk_nope_head_dim`` 128, ``qk_rope_head_dim``
64, ``v_head_dim`` 128; ``rope_theta`` 50000, ``rms_norm_eps`` 1e-5,
vocabulary 163,840, 8192 positions, untied embeddings, SiLU.

``head_dim`` is the query/key head size 128 + 64 = 192, so the softmax
scale is 1/sqrt(192) (no YaRN mscale: the config has no
``rope_scaling``). Departures from the HF modelling code: rope rotates
the pairs (i, i + 32) of the 64 rope features, where HF first
de-interleaves (2i, 2i + 1) — a fixed permutation of ``wq``'s and
``wkv_a``'s rope columns, immaterial for seeded weights; the learned
``e_score_correction_bias`` is not in the config and is drawn from the
seed (``models.moe.moe_init``).
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="moonshot-v1-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=192,
    d_ff=1408,
    vocab_size=163_840,
    rope_theta=50_000.0,
    norm_eps=1e-5,
    num_experts=64,
    top_k=6,
    num_shared_experts=2,
    router_score="sigmoid",
    router_bias=True,
    router_scale=2.446,
    first_dense_layers=1,
    dense_d_ff=11_264,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    grad_accum=8,
)


def reduced() -> ModelConfig:
    """The same block at CPU size: latent attention, one dense layer,
    sigmoid ``noaux_tc`` routing over 8 experts, a shared expert."""
    return CONFIG.replace(
        name="moonshot-smoke",
        num_layers=3,
        d_model=64,
        num_heads=4,
        num_kv_heads=4,
        head_dim=24,
        d_ff=32,
        dense_d_ff=96,
        vocab_size=512,
        num_experts=8,
        top_k=2,
        num_shared_experts=1,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        capacity_factor=8.0,  # drop-free at smoke-test sizes
        grad_accum=1,
    )
