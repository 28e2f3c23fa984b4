"""moonlight-16b-a3b: the DeepSeek-V3 block at 16B total / 3B active.
27L d_model=2048 16H, MLA (kv_lora 512, nope 128 + rope 64, v 128, no
q-LoRA), layer 0 a dense SwiGLU of 11264, layers 1-26 MoE: 64 routed
experts of 1408, top-6 by sigmoid + correction bias (noaux_tc, one
group), weights normalised and scaled by 2.446, 2 shared experts
(one SwiGLU of 2816); RoPE 50000 interleaved, RMSNorm eps 1e-5,
vocab 163840 untied, bf16 weights.
[hf:moonshotai/Moonlight-16B-A3B config.json]"""
from . import MLAConfig, ModelConfig, MoEConfig


def full_config() -> ModelConfig:
    return ModelConfig(
        name="moonlight-16b-a3b", family="moe",
        n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, head_dim=128,
        d_ff=11264, vocab=163840, norm_eps=1e-5, rope_theta=50000.0,
        rope_interleave=True, first_k_dense=1,
        mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                      qk_rope_head_dim=64, v_head_dim=128),
        moe=MoEConfig(n_experts=64, top_k=6, d_ff_expert=1408,
                      n_shared_experts=2, d_ff_shared=2816,
                      dispatch="dropless", router="sigmoid",
                      norm_topk=True, routed_scale=2.446),
        param_dtype="bfloat16",
        citation="hf:moonshotai/Moonlight-16B-A3B",
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="moonlight-16b-a3b-smoke", family="moe",
        n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
        d_ff=96, vocab=512, norm_eps=1e-5, rope_theta=50000.0,
        rope_interleave=True, first_k_dense=1,
        mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16,
                      qk_rope_head_dim=8, v_head_dim=16),
        moe=MoEConfig(n_experts=8, top_k=3, d_ff_expert=32,
                      n_shared_experts=2, d_ff_shared=64,
                      dispatch="dropless", router="sigmoid",
                      norm_topk=True, routed_scale=2.446),
        param_dtype="float32",
        attn_q_chunk=16, attn_k_chunk=16,
    )
