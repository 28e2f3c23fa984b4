"""Architecture configs (assigned pool) + input-shape sets.

Each assigned architecture lives in its own module as an exact
``ModelConfig`` (``full_config()``) plus a reduced same-family smoke
config (``smoke_config()``). Select with ``--arch <id>`` anywhere.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, Optional, Tuple

import jax.numpy as jnp


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared_experts: int = 0
    d_ff_shared: int = 0          # total hidden width of fused shared experts
    capacity_factor: float = 1.25  # "capacity" dispatch only
    # dispatch: "capacity" (GShard grouping; tokens past the capacity
    # drop — the training path) | "dropless" (serving: every routed
    # entry on an expert held here is computed, none is dropped)
    dispatch: str = "capacity"
    # router: "softmax" (top-k of the softmax, renormalised) |
    # "sigmoid" (DeepSeek-V3 noaux_tc with one group: top-k of
    # sigmoid + e_score_correction_bias, weights from the sigmoid)
    router: str = "softmax"
    norm_topk: bool = True         # renormalise the k chosen weights
    routed_scale: float = 1.0      # routed_scaling_factor
    # expert parallelism: the router spans ``n_router`` experts (0 =
    # n_experts); this layer holds ``n_experts`` of them, starting at
    # ``expert_offset``, and computes only their part of the result
    n_router: int = 0
    expert_offset: int = 0

    @property
    def router_experts(self) -> int:
        return self.n_router or self.n_experts


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2/V3), no q-LoRA: K and V
    of every head come from one cached latent row, ``c`` (RMS-normed
    ``kv_lora_rank`` values) and one shared RoPE key of
    ``qk_rope_head_dim``."""
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kv_norm_eps: float = 1e-6      # the latent's RMSNorm (library default)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def row(self) -> int:
        """Values of one token's cached latent row: ``c`` then k_pe."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def row_lanes(self) -> int:
        """The row padded to whole 128-lane tiles, as the pool stores it
        (the TPU lays the minor dim out in 128-lane tiles anyway)."""
        return -(-self.row // 128) * 128


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    kind: str                      # "mamba1" | "mamba2"
    d_state: int
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64             # mamba2 only
    chunk: int = 128               # scan chunk length
    scan_dtype: str = "float32"    # assoc-scan element dtype (perf knob)
    scan_impl: str = "assoc"       # assoc|blocked|kernel (mamba1 scan)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense|moe|ssm|hybrid|audio|vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0              # 0 -> d_model // n_heads
    qkv_bias: bool = False
    norm: str = "rmsnorm"          # rmsnorm|layernorm|nonparametric_ln
    tie_embeddings: bool = False
    rope_theta: float = 10000.0
    rope_interleave: bool = False  # RoPE pairs (2i, 2i+1), not (i, i+d/2)
    norm_eps: float = 0.0          # 0: the norm's own default
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    # leading layers whose FFN is a dense SwiGLU of width d_ff ahead of
    # the MoE stack (DeepSeek's first_k_dense_replace)
    first_k_dense: int = 0
    ssm: Optional[SSMConfig] = None
    # hybrid (zamba2): one shared attn+MLP block applied every k layers
    shared_attn_every: int = 0
    # enc-dec (whisper)
    encoder_layers: int = 0
    n_frames: int = 1500           # encoder input length (stub frontend)
    # vlm (internvl2)
    n_patches: int = 256           # patch embeddings (stub frontend)
    max_target_len: int = 448      # whisper decoder train length
    # numerics / execution
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    layer_loop: str = "scan"       # scan|paper_while|unroll
    save_policy: str = "all"       # all|offload|carry|carry_offload (§5.3)
    grad_accum: int = 1            # microbatches per step (in-graph loop)
    remat: str = "full"            # none|dots|full
    attn_impl: str = "xla"         # xla|pallas (pallas = TPU flash kernel)
    attn_q_chunk: int = 512
    attn_k_chunk: int = 1024
    attn_skip_masked_blocks: bool = False  # causal block skipping (§Perf)
    fuse_attn_mlp_allgather: bool = False  # beyond-paper opt (§Perf)
    # adaptive depth (models/adaptive.py): confidence-based early-exit
    # decode + mixture-of-depths token routing. Defaults keep both OFF
    # and every existing trace untouched.
    early_exit: bool = False       # decode layer loop gains a per-row
    #                                halt vector (core.while_loop)
    exit_threshold: float = float("inf")  # logit-margin (top1 - top2)
    #                                a row must clear to halt; inf =
    #                                machinery on, no row ever halts
    exit_min_layers: int = 1       # layers every row must run before
    #                                the halt check may fire
    mod_capacity: float = 0.0      # mixture-of-depths: fraction of
    #                                tokens processed per routed layer
    #                                (training top-capacity selection);
    #                                0 = off, no router params
    mod_every: int = 2             # layer i is routed iff
    #                                i % mod_every == mod_every - 1
    citation: str = ""

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(self.n_heads, 1)

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to 256 for MXU alignment + 16-way vocab sharding."""
        return _round_up(self.vocab, 256)

    @property
    def d_inner(self) -> int:
        return self.ssm.expand * self.d_model if self.ssm else 0

    @property
    def is_decoder_only(self) -> bool:
        return self.family in ("dense", "moe", "ssm", "hybrid", "vlm")

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def sub_quadratic(self) -> bool:
        """Eligible for long_500k (SSM / hybrid; see DESIGN.md)."""
        return self.family in ("ssm", "hybrid")

    def dtype(self, which: str):
        return jnp.dtype(getattr(self, which + "_dtype"))


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                      # train|prefill|decode


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}

ARCH_IDS: Tuple[str, ...] = (
    "dbrx-132b", "qwen2-moe-a2.7b", "zamba2-1.2b", "falcon-mamba-7b",
    "olmo-1b", "smollm-135m", "qwen2-7b", "llama3.2-1b",
    "whisper-small", "internvl2-1b",
)

_MODULES = {
    "dbrx-132b": "dbrx_132b",
    "qwen2-moe-a2.7b": "qwen2_moe_a2p7b",
    "zamba2-1.2b": "zamba2_1p2b",
    "falcon-mamba-7b": "falcon_mamba_7b",
    "olmo-1b": "olmo_1b",
    "smollm-135m": "smollm_135m",
    "qwen2-7b": "qwen2_7b",
    "llama3.2-1b": "llama3p2_1b",
    "whisper-small": "whisper_small",
    "internvl2-1b": "internvl2_1b",
    # served (bench/), outside the assigned dry-run pool
    "moonlight-16b-a3b": "moonlight_16b_a3b",
}


def get_config(arch: str, smoke: bool = False) -> ModelConfig:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch]}")
    return mod.smoke_config() if smoke else mod.full_config()


def cell_is_runnable(cfg: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Whether (arch, shape) is a valid dry-run cell (DESIGN.md §4)."""
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return False, ("skipped: full-attention arch; long_500k requires "
                       "sub-quadratic attention (DESIGN.md §Arch-applicability)")
    return True, ""
