"""moonlight-16b-a3b — DeepSeek-V3-style MoE with MLA, no q-LoRA.

Source: hf:moonshotai/Moonlight-16B-A3B config.json (``model_type``
deepseek_v3). 27 layers (the first dense, d_ff 11264), d_model 2048,
16 heads of multi-head latent attention with a full-rank q projection
(kv_lora 512, nope 128 / rope 64 / v 128), 64 routed experts of width
1408 top-6 plus 2 shared, sigmoid scores with a ranking-only correction
bias (``noaux_tc``, one group), top-6 weights normalized and scaled by
2.446, untied head, vocab 163840, rope_theta 50000, rms_norm_eps 1e-5.

The router computes its logits in float32, as the published gate
does. The layer holds all 64 experts here and drops past the capacity
factor; a chip's share of an expert-parallel deployment sets
``moe.held_experts`` to its range and computes that share dropless.
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    arch_type="moe",
    source="hf:moonshotai/Moonlight-16B-A3B (config.json)",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,               # MLA: kv heads == q heads post-expansion
    d_ff=11264,                    # the leading dense layer's FFN width
    vocab_size=163_840,
    norm_eps=1e-5,
    rope_theta=50_000.0,
    mla=MLAConfig(
        q_lora_rank=0,             # published q_lora_rank null
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
    ),
    moe=MoEConfig(
        num_experts=64,
        num_experts_per_tok=6,
        moe_d_ff=1408,
        num_shared_experts=2,
        first_k_dense=1,
        router_type="sigmoid",
        router_f32=True,
        routed_scaling_factor=2.446,
    ),
)
