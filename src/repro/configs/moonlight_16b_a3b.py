"""moonlight-16b-a3b [moe] — the DeepSeek-V3 block: multi-head latent attention
(no query LoRA, latent 512 + rotary 64) and 64 sigmoid-routed experts, top-6,
2 shared, first layer dense [hf:moonshotai/Moonlight-16B-A3B config.json]."""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=11264,  # the dense first layer
    vocab_size=163840,
    head_dim=128,  # v_head_dim; the attention widths are the mla_* fields
    moe_num_experts=64,
    moe_top_k=6,
    moe_num_shared=2,
    moe_d_ff=1408,
    moe_first_dense=1,
    moe_scoring="sigmoid",
    moe_norm_topk=True,
    moe_routed_scale=2.446,
    mla_kv_rank=512,
    mla_nope_dim=128,
    mla_rope_dim=64,
    mla_v_dim=128,
    rope_theta=50000.0,
    rope_interleave=True,
    norm_eps=1e-5,
)


def reduced() -> ModelConfig:
    return ModelConfig(
        name="moonlight-16b-a3b-reduced",
        family="moe",
        num_layers=3,
        d_model=64,
        num_heads=4,
        num_kv_heads=4,
        d_ff=96,
        vocab_size=256,
        head_dim=16,
        moe_num_experts=8,
        moe_top_k=2,
        moe_num_shared=1,
        moe_d_ff=32,
        moe_first_dense=1,
        moe_scoring="sigmoid",
        moe_norm_topk=True,
        moe_routed_scale=2.446,
        mla_kv_rank=32,
        mla_nope_dim=16,
        mla_rope_dim=16,
        mla_v_dim=16,
        rope_theta=50000.0,
        rope_interleave=True,
        norm_eps=1e-5,
        vocab_pad_multiple=8,
    )
