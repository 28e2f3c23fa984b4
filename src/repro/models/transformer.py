"""Decoder-only LM runner for dense / MoE / SSM / hybrid / VLM families.

Layer stacking: homogeneous blocks are stored with a leading ``layers``
dim and driven by one of three loop strategies (``cfg.layer_loop``):

- ``scan``        — ``lax.scan`` over stacked params (production default;
                    compile-time O(1) in depth).
- ``paper_while`` — ``repro.core.while_loop``: the paper's dynamic loop
                    hosting the production model; its stack-saving AD
                    (and ``save_policy="offload"`` host swapping, §5.3)
                    applies to the layer activations.
- ``unroll``      — static unrolling (the paper's Fig. 14 baseline).

All three produce identical math; tests assert gradient agreement.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from jax.ad_checkpoint import checkpoint_name

from .. import core
from ..dist import sharding as sh
from . import adaptive
from . import attention as attn_lib
from . import layers, moe as moe_lib, ssm as ssm_lib


# =========================== parameters ====================================

def attn_params(b, cfg, d_model: int):
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    p = {
        # fan-in is d_model: the default (second-to-last dim) would
        # take the head count and make random attention one-hot
        "wq": b.p((d_model, H, hd), (sh.EMBED, sh.HEADS, sh.HEAD_DIM),
                  fan_in=d_model),
        "wk": b.p((d_model, KV, hd), (sh.EMBED, sh.KV_HEADS, sh.HEAD_DIM),
                  fan_in=d_model),
        "wv": b.p((d_model, KV, hd), (sh.EMBED, sh.KV_HEADS, sh.HEAD_DIM),
                  fan_in=d_model),
        "wo": b.p((H, hd, d_model), (sh.HEADS, sh.HEAD_DIM, sh.EMBED),
                  fan_in=H * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = b.p((H, hd), (sh.HEADS, sh.HEAD_DIM), init="zeros")
        p["bk"] = b.p((KV, hd), (sh.KV_HEADS, sh.HEAD_DIM), init="zeros")
        p["bv"] = b.p((KV, hd), (sh.KV_HEADS, sh.HEAD_DIM), init="zeros")
    return p


def mlp_params(b, cfg, d_model: int, d_ff: int):
    return {
        "w_gate": b.p((d_model, d_ff), (sh.EMBED, sh.MLP)),
        "w_up": b.p((d_model, d_ff), (sh.EMBED, sh.MLP)),
        "w_down": b.p((d_ff, d_model), (sh.MLP, sh.EMBED), fan_in=d_ff),
    }


def _attn_block_params(b, cfg):
    p = {}
    p.update(layers.norm_params(b, cfg.norm, cfg.d_model, "ln_attn"))
    p.update({"attn": attn_params(b, cfg, cfg.d_model)})
    p.update(layers.norm_params(b, cfg.norm, cfg.d_model, "ln_mlp"))
    if cfg.family == "moe":
        p["moe"] = moe_lib.moe_params(b, cfg, cfg.d_model)
    else:
        p["mlp"] = mlp_params(b, cfg, cfg.d_model, cfg.d_ff)
    if adaptive.mod_on(cfg):
        p["router"] = adaptive.router_params(b, cfg)
    return p


def _ssm_block_params(b, cfg):
    p = {}
    p.update(layers.norm_params(b, cfg.norm, cfg.d_model, "ln"))
    if cfg.ssm.kind == "mamba1":
        p["ssm"] = ssm_lib.mamba1_params(b, cfg)
    else:
        p["ssm"] = ssm_lib.mamba2_params(b, cfg)
    return p


class _StackedBuilder:
    """Wrap a Builder so every param gains a leading (layers,) dim."""

    def __init__(self, b, n: int):
        self._b, self._n = b, n

    def p(self, shape, axes, **kw):
        return self._b.p((self._n, *shape), (sh.LAYERS, *axes), **kw)


def build_params(cfg, b):
    """Structure function used for init / abstract / axes (see params.py)."""
    Vp, D, L = cfg.padded_vocab, cfg.d_model, cfg.n_layers
    p: Dict[str, Any] = {
        "embed": b.p((Vp, D), (sh.VOCAB, sh.EMBED), init="normal", scale=0.02),
    }
    if cfg.family in ("dense", "moe", "vlm"):
        p["layers"] = _attn_block_params(_StackedBuilder(b, L), cfg)
    elif cfg.family == "ssm":
        p["layers"] = _ssm_block_params(_StackedBuilder(b, L), cfg)
    elif cfg.family == "hybrid":
        p["layers"] = _ssm_block_params(_StackedBuilder(b, L), cfg)
        p["shared_attn"] = _attn_block_params(b, cfg)   # ONE shared block
    else:
        raise ValueError(f"build_params: family {cfg.family}")
    p.update(layers.norm_params(b, cfg.norm, D, "ln_final"))
    if not cfg.tie_embeddings:
        p["unembed"] = b.p((D, Vp), (sh.EMBED, sh.VOCAB), init="normal",
                           scale=0.02)
    return p


# =========================== attention block ================================

def attn_apply(p, x, cfg, rules, *, positions, mode: str = "full",
               kv_cache=None, cur_len=None, chunk_off=None):
    """mode: full | prefill | chunk | decode | verify.
    Returns (out, new_kv | None).

    ``kv_cache`` (prefill/chunk/decode modes) is a KV-cache **layer
    view** (``repro.serve.kv_cache``): an object with ``write_prompt``
    / ``write_chunk`` / ``append`` / ``gather``, bound by the engine to
    this layer's slice of a dense or paged cache. The model never sees
    raw cache arrays — swapping cache layouts never touches this file.

    ``mode="chunk"`` is chunked prefill: ``x`` is a C-token slice of
    the prompt stream whose first token sits at per-row offset
    ``chunk_off`` (``positions`` must be the matching per-row absolute
    positions, ``chunk_off[:, None] + arange(C)``). The chunk's K/V is
    written at those offsets and attention runs against the CACHE
    (prior chunks included) — through the block table when
    ``cfg.attn_impl == "pallas"`` and the view is paged.

    ``mode="verify"`` is the speculative-decode verify window: same
    write path as ``"chunk"`` (the k+1 window's K/V lands at per-row
    ``chunk_off = cur_len - 1``, overwriting any stale rejected-draft
    lanes there), but attention runs ``verify_attention`` — per-
    position DECODE math, so greedy acceptance stays bitwise equal to
    sequential decode (see ``models.attention.verify_attention``).
    """
    cdt = cfg.dtype("compute")
    xc = x.astype(cdt)
    q = jnp.einsum("bsd,dhk->bshk", xc, p["wq"].astype(cdt))
    k = jnp.einsum("bsd,dhk->bshk", xc, p["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bshk", xc, p["wv"].astype(cdt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cdt)
        k = k + p["bk"].astype(cdt)
        v = v + p["bv"].astype(cdt)
    q = layers.rope(q, positions, cfg.rope_theta)
    k = layers.rope(k, positions, cfg.rope_theta)

    # Sequence-parallel attention (DESIGN.md: head-count fallback): q's
    # S dim shards over `model`; K/V replicate (one all-gather); the
    # online-softmax loop then runs with zero internal collectives.
    seq_tp = (rules is not None
              and rules.mesh_axes(sh.ATTN_SEQ) is not None
              and mode in ("full", "prefill") and q.shape[1] > 1)
    if seq_tp:
        q = sh.constrain(q, rules, (sh.BATCH, sh.ATTN_SEQ, None, None))
        k = sh.constrain(k, rules, (sh.BATCH, None, None, None))
        v = sh.constrain(v, rules, (sh.BATCH, None, None, None))
        q_chunk_eff = q.shape[1]        # single q block; GSPMD splits S
    else:
        q_chunk_eff = cfg.attn_q_chunk

    new_kv = None
    use_pallas = (cfg.attn_impl == "pallas" and not seq_tp
                  and mode == "full"
                  and q.shape[1] % 128 == 0 and k.shape[1] % 128 == 0)
    if mode == "full":
        if use_pallas:
            from ..kernels.flash_attention.ops import flash_attention
            out = flash_attention(q, k, v, causal=True)
        else:
            out = attn_lib.chunked_attention(
                q, k, v, causal=True, q_chunk=q_chunk_eff,
                k_chunk=cfg.attn_k_chunk,
                skip_masked_blocks=(cfg.attn_skip_masked_blocks
                                    and not seq_tp))
    elif mode == "prefill":
        new_kv = kv_cache.write_prompt(k, v)
        out = attn_lib.chunked_attention(
            q, k, v, causal=True, q_chunk=q_chunk_eff,
            k_chunk=cfg.attn_k_chunk,
            skip_masked_blocks=(cfg.attn_skip_masked_blocks
                                and not seq_tp))
    elif mode == "chunk":
        # Write the chunk's K/V at its per-row offsets FIRST, then
        # attend against the cache — prior chunks and this one stream
        # back through whatever layout the view owns (block-table
        # kernel under attn_impl="pallas" + paged; gather otherwise).
        new_kv = kv_cache.write_chunk(k, v, chunk_off)
        out = attn_lib.prefill_attention(q, new_kv, q_off=chunk_off,
                                         attn_impl=cfg.attn_impl,
                                         k_chunk=cfg.attn_k_chunk)
    elif mode == "verify":
        # Speculative verify: write the whole k+1 window at the slot's
        # pending position FIRST (stale rejected-draft K/V from the
        # previous window is rewritten before any query sees it), then
        # score every position with decode-exact attention.
        new_kv = kv_cache.write_chunk(k, v, chunk_off)
        out = attn_lib.verify_attention(q, new_kv, q_off=chunk_off,
                                        attn_impl=cfg.attn_impl)
    elif mode == "decode":
        # The incoming token's K/V lands at cur_len - 1 (per-row depths
        # under slot-based continuous batching); the view routes the
        # write through whatever layout it owns (dense column scatter,
        # or paged block-table scatter).
        new_kv = kv_cache.append(k, v, cur_len)
        # attn_impl="pallas" + a paged view = the gather-free Pallas
        # paged-attention kernel; anything else gathers (dense views
        # gather for free).
        out = attn_lib.decode_attention(q, new_kv, cur_len=cur_len,
                                        attn_impl=cfg.attn_impl)
    else:
        raise ValueError(mode)

    if seq_tp:
        out = sh.constrain(out, rules, (sh.BATCH, sh.ATTN_SEQ, None, None))
    out = jnp.einsum("bshk,hkd->bsd", out.astype(cdt), p["wo"].astype(cdt))
    out = out.astype(x.dtype)
    if seq_tp:
        out = sh.constrain(out, rules, (sh.BATCH, None, None))
    return out, new_kv


def attn_block(p, x, cfg, rules, *, positions, mode="full", kv_cache=None,
               cur_len=None, chunk_off=None):
    """Pre-norm attention + (MoE|MLP) block. Returns (x, new_kv, aux)."""
    h = layers.apply_norm(cfg.norm, x, p, "ln_attn")
    a, new_kv = attn_apply(p["attn"], h, cfg, rules, positions=positions,
                           mode=mode, kv_cache=kv_cache, cur_len=cur_len,
                           chunk_off=chunk_off)
    a = checkpoint_name(a, "attn_out")
    x = x + a
    h = layers.apply_norm(cfg.norm, x, p, "ln_mlp")
    if cfg.family == "moe":
        m, aux = moe_lib.moe_mlp(p["moe"], h, cfg, rules)
    else:
        m = layers.swiglu(h, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                          p["mlp"]["w_down"], cfg.dtype("compute"))
        aux = {}
    x = x + m.astype(x.dtype)
    x = sh.constrain(x, rules, (sh.BATCH, None, None))
    return x, new_kv, aux


def ssm_block(p, x, cfg, rules, *, mode="full", state=None):
    """Pre-norm mamba block. Returns (x, new_state)."""
    h = layers.apply_norm(cfg.norm, x, p, "ln")
    if mode == "full":
        fwd = (ssm_lib.mamba1_forward if cfg.ssm.kind == "mamba1"
               else ssm_lib.mamba2_forward)
        y = fwd(p["ssm"], h, cfg, rules)
        new_state = None
    else:  # decode: single token
        step = (ssm_lib.mamba1_step if cfg.ssm.kind == "mamba1"
                else ssm_lib.mamba2_step)
        y, new_state = step(p["ssm"], h[:, 0], state, cfg)
        y = y[:, None]
    x = x + y
    x = sh.constrain(x, rules, (sh.BATCH, None, None))
    return x, new_state


# =========================== layer loops ====================================

def _remat(fn, cfg):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    if cfg.remat == "attn_out":
        # selective: save only the (tagged) attention outputs — skips
        # recomputing attention in backward at a bf16 (B,S,D)/layer cost,
        # while the MLP still rematerializes (§Perf iteration 14).
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.save_only_these_names(
                "attn_out"))
    return jax.checkpoint(fn)  # "full": save only block inputs


def _run_layers(stacked, x, cfg, rules, block_fn, aux0):
    """Drive the homogeneous layer stack per cfg.layer_loop.

    block_fn(layer_params, x, i) -> (x, aux_delta) — ``i`` is the layer
    index (traced under scan/paper_while), used by layer-position-
    dependent features (mixture-of-depths routing).

    The inter-block residual stream is stored SEQUENCE-SHARDED over the
    `model` axis (Korthikanti-style sequence parallelism): the layer
    loop's saved/offloaded per-layer activation is 1/model_size of the
    bytes; the all-gather back to full S happens inside the rematted
    step, so backward recompute re-gathers instead of re-storing.
    """

    def step(carry, xs):
        lp, i = xs
        x, aux = carry
        x = sh.constrain(x, rules, (sh.BATCH, None, None))
        x, d = block_fn(lp, x, i)
        x = sh.constrain(x, rules, (sh.BATCH, sh.ACT_SEQ, None))
        return (x, jax.tree.map(jnp.add, aux, d)), None

    step = _remat(step, cfg)
    n = jax.tree.leaves(stacked)[0].shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)

    x = sh.constrain(x, rules, (sh.BATCH, sh.ACT_SEQ, None))
    if cfg.layer_loop == "scan":
        (x, aux), _ = jax.lax.scan(step, (x, aux0), (stacked, idx))
        x = sh.constrain(x, rules, (sh.BATCH, None, None))
        return x, aux
    if cfg.layer_loop == "paper_while":
        def body(i, carry):
            lp = jax.tree.map(lambda a: a[i], stacked)
            return step(carry, (lp, i))[0]
        offl = None
        if rules is not None and rules.mesh is not None and \
                cfg.save_policy in ("offload", "carry_offload"):
            offl = (rules.sharding((sh.BATCH, sh.ACT_SEQ, None)),
                    jax.tree.map(lambda _: rules.sharding(()), aux0))
        x, aux = core.fori_loop(0, n, body, (x, aux0),
                                save_policy=cfg.save_policy,
                                offload_shardings=offl)
        return sh.constrain(x, rules, (sh.BATCH, None, None)), aux
    if cfg.layer_loop == "unroll":
        carry = (x, aux0)
        for i in range(n):
            lp = jax.tree.map(lambda a: a[i], stacked)
            carry = step(carry, (lp, jnp.int32(i)))[0]
        x, aux = carry
        return sh.constrain(x, rules, (sh.BATCH, None, None)), aux
    raise ValueError(cfg.layer_loop)


def kv_project_append(p, h, cfg, kv_cache, positions, cur_len):
    """K/V projection + cache append ONLY — ``attn_apply``'s decode
    write path without q, attention, or the output projection.

    This is the skipped-layer KV fill of early-exit decode: a row that
    halted at layer ``e`` still owes the cache K/V for layers
    ``e..L-1`` so later full-depth tokens can attend to this position
    at every layer. The ops mirror ``attn_apply`` line-for-line, so a
    layer filled from hidden state ``h`` holds bit-identical K/V to one
    whose full block ran on the same ``h`` — which is exactly the
    standard early-exit propagation rule: project the halting layer's
    (normed) hidden state into every remaining layer's cache.
    ``h`` must already be this layer's ``ln_attn`` output.
    """
    cdt = cfg.dtype("compute")
    xc = h.astype(cdt)
    k = jnp.einsum("bsd,dhk->bshk", xc, p["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bshk", xc, p["wv"].astype(cdt))
    if cfg.qkv_bias:
        k = k + p["bk"].astype(cdt)
        v = v + p["bv"].astype(cdt)
    k = layers.rope(k, positions, cfg.rope_theta)
    return kv_cache.append(k, v, cur_len)


def decode_layers(stacked, x, leaves, cfg, *, block_fn, halt_fn=None,
                  kv_fill_fn=None, live=None):
    """Drive the decode-mode layer stack (single-token step).

    ``block_fn(lp, lv, x, i) -> (x_new, new_leaves, applied)`` runs one
    decoder block at layer ``i`` against its per-layer KV leaves ``lv``
    and MUST always perform its KV append — a row whose block output is
    masked off (mixture-of-depths skip, early-exit halt) still writes
    K/V projected from its frozen hidden state (see ``models.attention``
    on skipped-layer KV semantics). ``applied`` (B,) bool reports which
    rows' residual stream actually advanced (MoD skips return False);
    it feeds the per-row depth stat only — the block applies its own
    masking in the static paths.

    ``halt_fn(x, i) -> (B,) bool`` (early exit) marks rows allowed to
    halt AFTER layer ``i``. None => depth is static and the loop runs
    per ``cfg.layer_loop`` (scan default — op-for-op the engine's
    historical decode scan). Non-None => the loop becomes a
    ``core.while_loop`` whose VECTOR predicate ``(i < L) & ~halted``
    keeps iterating while ANY row is live; the halt carry is updated
    ``halted |= halt_fn(x, i)`` so a halted row can never un-halt, and
    halted rows carry ``x`` through unchanged (their block still ran —
    KV propagation — but its output is discarded). After the loop, a
    second while (``kv_fill_fn(lp, lv, x, i) -> new_leaves``;
    projection-only, ZERO attention FLOPs) fills layers ``i_exit..L-1``
    for every row so the cache is complete at full depth.

    ``live`` (B,) bool: rows that should participate in the dynamic
    predicate (retired slots pass False and start halted). Ignored in
    the static paths.

    Returns ``(x, new_leaves, depth)`` — depth (B,) int32 counts blocks
    applied per row (== L everywhere when nothing is adaptive).
    """
    n = jax.tree.leaves(stacked)[0].shape[0]
    B = x.shape[0]
    depth0 = jnp.zeros((B,), jnp.int32)

    def put(lvs, i, new_lv):
        return jax.tree.map(
            lambda full, nl: full.at[i].set(nl.astype(full.dtype)),
            lvs, new_lv)

    if halt_fn is None:
        if cfg.layer_loop == "scan":
            def f(carry, xs):
                xx, depth = carry
                lp, lv, i = xs
                xx, new_lv, applied = block_fn(lp, lv, xx, i)
                return (xx, depth + applied.astype(jnp.int32)), new_lv
            idx = jnp.arange(n, dtype=jnp.int32)
            (x, depth), new_leaves = jax.lax.scan(
                f, (x, depth0), (stacked, leaves, idx))
            return x, new_leaves, depth
        if cfg.layer_loop in ("paper_while", "unroll"):
            def body(i, carry):
                xx, lvs, depth = carry
                lp = jax.tree.map(lambda a: a[i], stacked)
                lv = jax.tree.map(lambda a: a[i], lvs)
                xx, new_lv, applied = block_fn(lp, lv, xx, i)
                return xx, put(lvs, i, new_lv), \
                    depth + applied.astype(jnp.int32)
            if cfg.layer_loop == "unroll":
                carry = (x, leaves, depth0)
                for i in range(n):
                    carry = body(jnp.int32(i), carry)
                return carry
            return core.fori_loop(0, n, body, (x, leaves, depth0))
        raise ValueError(cfg.layer_loop)

    # --- adaptive: data-dependent per-row depth (paper §3.1: the
    # conditional lives in-graph; the host never sees the halt bits) ---
    if kv_fill_fn is None:
        raise ValueError("decode_layers: halt_fn requires kv_fill_fn "
                         "(skipped-layer KV propagation)")
    halted0 = jnp.zeros((B,), bool) if live is None else ~live

    def cond(c):
        i, _, _, halted, _ = c
        return (i < n) & ~halted          # vector: run while ANY row live

    def body(c):
        i, xx, lvs, halted, depth = c
        lp = jax.tree.map(lambda a: a[i], stacked)
        lv = jax.tree.map(lambda a: a[i], lvs)
        x_new, new_lv, applied = block_fn(lp, lv, xx, i)
        applied = applied & ~halted
        xx = jnp.where(applied[:, None, None], x_new, xx)
        depth = depth + applied.astype(jnp.int32)
        halted = halted | halt_fn(xx, i)
        return (i + 1, xx, put(lvs, i, new_lv), halted, depth)

    i, x, leaves, halted, depth = core.while_loop(
        cond, body, (jnp.int32(0), x, leaves, halted0, depth0),
        max_iters=n, name="adaptive_layers")

    # KV-fill tail: layers i..L-1 get K/V projected from the frozen x
    # for EVERY row (no q / attention / MLP — projection + append only).
    def fill_cond(c):
        return c[0] < n

    def fill_body(c):
        j, lvs = c
        lp = jax.tree.map(lambda a: a[j], stacked)
        lv = jax.tree.map(lambda a: a[j], lvs)
        new_lv = kv_fill_fn(lp, lv, x, j)
        return (j + 1, put(lvs, j, new_lv))

    _, leaves = core.while_loop(fill_cond, fill_body, (i, leaves),
                                max_iters=n, name="kv_fill")
    return x, leaves, depth


# =========================== forward passes =================================

def _embed_tokens(p, tokens, cfg, rules, prefix_embeds=None):
    cdt = cfg.dtype("compute")
    x = jnp.take(p["embed"].astype(cdt), tokens, axis=0)
    if prefix_embeds is not None:  # VLM: prepend patch embeddings
        x = jnp.concatenate([prefix_embeds.astype(cdt), x], axis=1)
    return sh.constrain(x, rules, (sh.BATCH, None, None))


def _hybrid_layers(p, x, cfg, rules, block_kw=None):
    """zamba2: shared attn block every k mamba2 layers (DESIGN.md §10)."""
    k = cfg.shared_attn_every
    L = cfg.n_layers
    aux: Dict[str, jax.Array] = {}
    positions = jnp.arange(x.shape[1])[None]
    n_apps = 0
    for start in range(0, L, k):
        x, _, _ = attn_block(p["shared_attn"], x, cfg, rules,
                             positions=positions, mode="full")
        n_apps += 1
        seg = jax.tree.map(lambda a: a[start:min(start + k, L)], p["layers"])

        def block_fn(lp, xx, i):
            return ssm_block(lp, xx, cfg, rules, mode="full")[0], {}

        x, _ = _run_layers(seg, x, cfg, rules, block_fn, {})
    return x, aux


def forward_features(params, cfg, tokens, *, rules=None, prefix_embeds=None
                     ) -> Tuple[jax.Array, Dict]:
    """Backbone + final norm, NO unembed. Returns (features, aux).

    Training uses this + a chunked unembed/CE (model_zoo._chunked_ce) so
    the (B, S, V) fp32 logits are never materialized whole.
    """
    x = _embed_tokens(params, tokens, cfg, rules, prefix_embeds)
    S = x.shape[1]
    positions = jnp.arange(S)[None]

    if cfg.family == "hybrid":
        x, aux = _hybrid_layers(params, x, cfg, rules)
    elif cfg.family == "ssm":
        def block_fn(lp, xx, i):
            return ssm_block(lp, xx, cfg, rules, mode="full")[0], {}
        x, aux = _run_layers(params["layers"], x, cfg, rules, block_fn, {})
    else:
        aux0 = ({"moe_load_balance": jnp.float32(0.0),
                 "moe_z_loss": jnp.float32(0.0)}
                if cfg.family == "moe" else {})

        def block_fn(lp, xx, i):
            x2, _, aux = attn_block(lp, xx, cfg, rules, positions=positions,
                                    mode="full")
            if adaptive.mod_on(cfg):
                # router weight scales the kept delta -> differentiable
                x2 = adaptive.mod_apply_full(lp["router"], xx, x2, i, cfg)
            return x2, aux
        x, aux = _run_layers(params["layers"], x, cfg, rules, block_fn, aux0)

    return layers.apply_norm(cfg.norm, x, params, "ln_final"), aux


def unembed_weight(params, cfg):
    return params["embed"].T if cfg.tie_embeddings else params["unembed"]


def forward(params, cfg, tokens, *, rules=None, prefix_embeds=None
            ) -> Tuple[jax.Array, Dict]:
    """Full-sequence forward (evaluation / tests). Returns (logits, aux)."""
    x, aux = forward_features(params, cfg, tokens, rules=rules,
                              prefix_embeds=prefix_embeds)
    cdt = cfg.dtype("compute")
    w = unembed_weight(params, cfg).astype(cdt)
    logits = jnp.einsum("bsd,dv->bsv", x.astype(cdt), w)
    logits = sh.constrain(logits, rules, (sh.BATCH, None, sh.VOCAB))
    return logits, aux
