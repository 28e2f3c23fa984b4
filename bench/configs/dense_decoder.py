"""The dense decoder family: a configuration file of this family onto
the program's ``ModelConfig``, the scale of each drawn weight, and the
operations and bytes the served model needs.

The counting uses the configuration file's sizes and token counts the
client observed; nothing is read from the program. Model FLOPs count
what the mathematics of the requests needs: rows the program computes
for masked slots, and logits at prompt positions other than the last,
are not model FLOPs.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from flops import BF16, attn_pairs


def program_config(c: dict):
    """The program's ``ModelConfig`` for configuration file ``c``: the
    registered architecture with the file's sizes and the Pallas path."""
    from repro.configs import get_config
    norm = {"nonparametric": "nonparametric_ln", "rms": "rmsnorm"}
    cfg = get_config(c["program_arch"])
    return dataclasses.replace(
        cfg, n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        rope_theta=c["rope_theta"], qkv_bias=c["attention_bias"],
        tie_embeddings=c["tie_word_embeddings"], norm=norm[c["layer_norm"]],
        param_dtype=c["param_dtype"], compute_dtype=c["compute_dtype"],
        attn_impl="pallas")


def leaf_std(name: str, shape, c: dict):
    """Standard deviation of the weight leaf ``name``; None for a norm
    gain, drawn around 1. Fan-in scales (the program's own rule), so
    the attention of random weights is not one-hot; biases are drawn
    wide so that the comparison exercises them."""
    if name in ("embed", "unembed"):
        return 0.02
    if name in ("wq", "wk", "wv", "w_gate", "w_up"):
        return 1.0 / np.sqrt(c["hidden_size"])
    if name == "wo":
        return 1.0 / np.sqrt(shape[-3] * shape[-2])
    if name == "w_down":
        return 1.0 / np.sqrt(shape[-2])
    if name in ("bq", "bk", "bv"):
        return 0.5
    return None


def sizes(c: dict) -> dict:
    """Derived sizes of configuration file ``c``."""
    d, h, kv = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    hd, f, n_l = c["head_dim"], c["intermediate_size"], \
        c["num_hidden_layers"]
    vp = -(-c["vocab_size"] // 256) * 256        # the program pads to 256
    attn = d * (h + 2 * kv) * hd + h * hd * d
    mlp = 3 * d * f
    return {"d": d, "h": h, "kv": kv, "hd": hd, "layers": n_l,
            "vocab_padded": vp, "layer_matmul_params": attn + mlp,
            "kv_bytes_per_token": n_l * 2 * kv * hd * BF16}


def prefill_flops(c: dict, start: int, n: int) -> float:
    """Model FLOPs of prefilling ``n`` prompt tokens from ``start``
    (the prompt's last position's logits included)."""
    s = sizes(c)
    dense = 2 * s["layer_matmul_params"] * s["layers"] * n
    attn = 4 * s["h"] * s["hd"] * s["layers"] * attn_pairs(start, n)
    head = 2 * s["d"] * s["vocab_padded"]
    return float(dense + attn + head)


def decode_flops(c: dict, context: int) -> float:
    """Model FLOPs of one decode token attending over ``context``
    positions (its own included)."""
    s = sizes(c)
    dense = 2 * s["layer_matmul_params"] * s["layers"]
    attn = 4 * s["h"] * s["hd"] * s["layers"] * context
    head = 2 * s["d"] * s["vocab_padded"]
    return float(dense + attn + head)


def paged_attention_work(c: dict, context: int) -> tuple:
    """(FLOPs, bytes) the decode-attention kernel needs for one token
    over ``context`` cached positions, summed over layers: q.k and p.v
    for every head, and the K/V of every position read once."""
    s = sizes(c)
    flops = 4 * s["h"] * s["hd"] * context * s["layers"]
    qo = 2 * s["h"] * s["hd"] * BF16 * s["layers"]
    return float(flops), float(context * s["kv_bytes_per_token"] + qo)


def flash_prefill_work(c: dict, start: int, n: int) -> tuple:
    """(FLOPs, bytes) the chunk-prefill attention kernel needs for a
    chunk of ``n`` real queries at ``start``, summed over layers: the
    causal pairs, the K/V of positions ``0 .. start+n-1`` read once,
    and the queries read and outputs written once."""
    s = sizes(c)
    flops = 4 * s["h"] * s["hd"] * attn_pairs(start, n) * s["layers"]
    kv = (start + n) * s["kv_bytes_per_token"]
    qo = 2 * n * s["h"] * s["hd"] * BF16 * s["layers"]
    return float(flops), float(kv + qo)
