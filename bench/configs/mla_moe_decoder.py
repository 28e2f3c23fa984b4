"""The DeepSeek-V3 decoder family (latent attention, a mixture of
experts with shared experts, leading dense layers): a configuration
file of this family onto the program's ``ModelConfig``, the scale of
each drawn weight, and the operations and bytes the served model needs.

The counting uses the configuration file's sizes and token counts the
client observed; nothing is read from the program. Model FLOPs count
this chip's share of the model's mathematics per token: the latent
attention in its absorbed form (the projections, ``q_nope W_UK^T``,
the scores of ``kv_lora_rank + qk_rope_head_dim`` lanes and the
weighted sum of ``kv_lora_rank`` lanes per head and position, ``W_UV``
and the output projection), the dense layers, and per MoE layer the
router, the shared experts and ``num_experts_per_tok *
n_routed_experts / router_experts`` routed experts: the entries that
fall on the experts held here under uniform routing. Rows computed for
masked slots, and logits at prompt positions other than the last, are
not model FLOPs.

A latent row is counted at its ``kv_lora_rank + qk_rope_head_dim``
values; the pool stores it padded to whole 128-lane tiles.
"""

from __future__ import annotations

import dataclasses

from flops import BF16, attn_pairs

# e_score_correction_bias: drawn wide enough that choosing by s + bias
# and weighting by s differ on most tokens (PERF.md §4)
BIAS_STD = 0.05


def program_config(c: dict):
    """The program's ``ModelConfig`` for configuration file ``c``: the
    registered architecture with the file's sizes and share, and the
    Pallas path."""
    from repro.configs import MLAConfig, get_config
    cfg = get_config(c["program_arch"])
    moe = dataclasses.replace(
        cfg.moe, n_experts=c["n_routed_experts"],
        top_k=c["num_experts_per_tok"], d_ff_expert=c["moe_intermediate_size"],
        n_shared_experts=c["n_shared_experts"],
        d_ff_shared=c["n_shared_experts"] * c["moe_intermediate_size"],
        norm_topk=c["norm_topk_prob"],
        routed_scale=c["routed_scaling_factor"],
        n_router=c["router_experts"], expert_offset=c["expert_offset"])
    mla = MLAConfig(kv_lora_rank=c["kv_lora_rank"],
                    qk_nope_head_dim=c["qk_nope_head_dim"],
                    qk_rope_head_dim=c["qk_rope_head_dim"],
                    v_head_dim=c["v_head_dim"])
    return dataclasses.replace(
        cfg, n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        first_k_dense=c["first_k_dense_replace"], moe=moe, mla=mla,
        tie_embeddings=c["tie_word_embeddings"],
        param_dtype=c["param_dtype"], compute_dtype=c["compute_dtype"],
        attn_impl="pallas")


def leaf_std(name: str, shape, c: dict):
    """Standard deviation of the weight leaf ``name``; None for a norm
    gain, drawn around 1. Fan-in scales, as the program's own rule."""
    if name in ("embed", "unembed"):
        return 0.02
    if name in ("wq", "wkv_a", "w_gate", "w_up", "shared_gate", "shared_up",
                "router"):
        return 1.0 / c["hidden_size"] ** 0.5
    if name in ("wk_b", "wv_b"):
        return 1.0 / c["kv_lora_rank"] ** 0.5
    if name == "wo":
        return 1.0 / (shape[-3] * shape[-2]) ** 0.5
    if name in ("w_down", "shared_down"):
        return 1.0 / shape[-2] ** 0.5
    if name == "e_score_correction_bias":
        return BIAS_STD
    return None


def sizes(c: dict) -> dict:
    """Derived sizes of configuration file ``c``."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    r, v = c["kv_lora_rank"], c["v_head_dim"]
    n_l, k = c["num_hidden_layers"], c["first_k_dense_replace"]
    f_e = c["moe_intermediate_size"]
    routed = (c["num_experts_per_tok"] * c["n_routed_experts"]
              / c["router_experts"])
    # matmul weights a token multiplies, per layer
    attn = d * h * (nope + rope) + d * (r + rope) + h * nope * r \
        + h * r * v + h * v * d
    dense = 3 * d * c["intermediate_size"]
    expert = 3 * d * f_e
    moe = d * c["router_experts"] + c["n_shared_experts"] * expert \
        + routed * expert
    return {"d": d, "h": h, "layers": n_l, "dense_layers": k,
            "moe_layers": n_l - k, "row": r + rope, "value": r,
            "vocab_padded": -(-c["vocab_size"] // 256) * 256,
            "token_matmul_params": n_l * attn + k * dense + (n_l - k) * moe,
            "expert_params": expert, "held": c["n_routed_experts"],
            "kv_bytes_per_token": n_l * (r + rope) * BF16}


def _pair_flops(s: dict) -> float:
    """FLOPs of one (query, position) pair, all heads and layers:
    scores over the row, the weighted sum of its value lanes."""
    return 2 * s["h"] * (s["row"] + s["value"]) * s["layers"]


def prefill_flops(c: dict, start: int, n: int) -> float:
    """Model FLOPs of prefilling ``n`` prompt tokens from ``start``
    (the prompt's last position's logits included)."""
    s = sizes(c)
    return float(2 * s["token_matmul_params"] * n
                 + _pair_flops(s) * attn_pairs(start, n)
                 + 2 * s["d"] * s["vocab_padded"])


def decode_flops(c: dict, context: int) -> float:
    """Model FLOPs of one decode token attending over ``context``
    positions (its own included)."""
    s = sizes(c)
    return float(2 * s["token_matmul_params"] + _pair_flops(s) * context
                 + 2 * s["d"] * s["vocab_padded"])


def _qo_bytes(s: dict) -> int:
    """One token's absorbed query (row lanes) and output (value lanes)
    per head, summed over layers."""
    return s["h"] * (s["row"] + s["value"]) * BF16 * s["layers"]


def paged_attention_work(c: dict, context: int) -> tuple:
    """(FLOPs, bytes) the decode-attention kernel needs for one token
    over ``context`` cached positions, summed over layers: the absorbed
    scores and weighted sum of every head, each latent row read once."""
    s = sizes(c)
    return (float(_pair_flops(s) * context),
            float(context * s["kv_bytes_per_token"] + _qo_bytes(s)))


def flash_prefill_work(c: dict, start: int, n: int) -> tuple:
    """(FLOPs, bytes) the chunk-prefill attention kernel needs for a
    chunk of ``n`` real queries at ``start``, summed over layers: the
    causal pairs in absorbed form, the latent rows of positions
    ``0 .. start+n-1`` read once, queries read and outputs written
    once."""
    s = sizes(c)
    return (float(_pair_flops(s) * attn_pairs(start, n)),
            float((start + n) * s["kv_bytes_per_token"] + n * _qo_bytes(s)))


def gmm_work(c: dict, entries: int, calls: int) -> tuple:
    """(FLOPs, bytes) the grouped expert matmuls need for ``entries``
    routed entries on the experts held here, over ``calls`` layer calls
    (each three ``gmm`` kernels: gate, up, down): the three products
    per entry, every held expert's weights read once per call (at the
    cell's batch every held expert has entries in every call), and each
    entry's rows read and written once."""
    s = sizes(c)
    d, f = s["d"], s["expert_params"] // (3 * s["d"])
    flops = 2 * s["expert_params"] * entries
    weights = calls * s["held"] * s["expert_params"] * BF16
    rows = entries * 3 * (d + f) * BF16
    return float(flops), float(weights + rows)
