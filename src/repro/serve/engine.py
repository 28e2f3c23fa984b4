"""Serving engine: caches, prefill, single-token decode, and the
**in-graph generation loops** built on the paper's dynamic control
flow — a ``repro.core.while_loop`` with data-dependent exits, the
inference-side counterpart of the paper's §2.2 applications ("the
entire computation stays inside the system runtime").

Two generation paths (DESIGN.md §7):

- ``generate_batch_sync`` — batch-synchronous in-graph loop with
  per-sequence EOS early exit (jittable reference).
- ``generate`` — compatibility wrapper over the slot-based
  continuous-batching scheduler (``repro.serve.scheduler``), which
  retires and refills decode slots mid-stream.

Self-attention K/V state lives behind the ``repro.serve.kv_cache``
protocol (DESIGN.md §8): ``make_cache`` builds a family-shaped dict
whose attention entries are ``KVCache`` objects (dense or paged), and
the decode/prefill paths thread per-layer **views** of those objects
through the model code — the model never sees raw cache arrays, so the
two layouts share every line of attention math. SSM conv/h state and
the audio cross-attention cache stay plain per-row arrays (they are
O(1)-per-token or fixed-width — paging buys nothing), with the batch
dim at axis 1 of every leaf: the invariant the scheduler's admission
splice relies on for those parts.

``decode_step`` accepts a scalar ``cur_len`` (whole batch in lockstep)
or a per-row vector (slot pool at mixed depths).
"""

from __future__ import annotations

import collections
import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import core
from ..configs import ModelConfig
from ..dist import sharding as sh
from ..models import adaptive, encdec, layers, ssm as ssm_lib, transformer
from . import kv_cache as kvc


# =========================== cache construction =============================

def _ssm_struct(cfg, batch: int, mode: str):
    s = cfg.ssm
    L = cfg.n_layers
    di = cfg.d_inner
    if s.kind == "mamba1":
        conv_shape = (L, batch, s.d_conv - 1, di)
        h_shape = (L, batch, di, s.d_state)
        h_axes = (sh.LAYERS, sh.BATCH, sh.INNER, sh.STATE)
    else:
        H = di // s.head_dim
        conv_shape = (L, batch, s.d_conv - 1, di + 2 * s.d_state)
        h_shape = (L, batch, H, s.head_dim, s.d_state)
        h_axes = (sh.LAYERS, sh.BATCH, sh.INNER, None, sh.STATE)
    conv_axes = (sh.LAYERS, sh.BATCH, None, sh.INNER)
    if mode == "abstract":
        return {"conv": jax.ShapeDtypeStruct(conv_shape, cfg.dtype("compute")),
                "h": jax.ShapeDtypeStruct(h_shape, jnp.float32)}
    if mode == "axes":
        return {"conv": conv_axes, "h": h_axes}
    return {"conv": jnp.zeros(conv_shape, cfg.dtype("compute")),
            "h": jnp.zeros(h_shape, jnp.float32)}


def _cross_struct(cfg, batch: int, mode: str):
    KV, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    shape = (cfg.n_layers, batch, cfg.n_frames, KV, hd)
    axes = (sh.LAYERS, sh.BATCH, None, sh.CACHE_KV, sh.CACHE_HD)
    if mode == "abstract":
        e = jax.ShapeDtypeStruct(shape, cfg.dtype("compute"))
        return {"k": e, "v": e}
    if mode == "axes":
        return {"k": axes, "v": axes}
    z = jnp.zeros(shape, cfg.dtype("compute"))
    return {"k": z, "v": z}


def _n_shared_apps(cfg) -> int:
    return math.ceil(cfg.n_layers / cfg.shared_attn_every)


def kv_key(cfg: ModelConfig) -> Optional[str]:
    """Cache-dict key of the family's self-attention ``KVCache`` (None
    for pure-SSM families, which have no attention K/V)."""
    return {"dense": "attn", "moe": "attn", "vlm": "attn",
            "hybrid": "attn", "audio": "self", "ssm": None}[cfg.family]


def _attn_layer_count(cfg) -> int:
    return _n_shared_apps(cfg) if cfg.family == "hybrid" else cfg.n_layers


def make_cache(cfg: ModelConfig, batch: int, max_len: int,
               mode: str = "init", *, kv_impl: str = "dense",
               kv_block: int = 16, kv_blocks: Optional[int] = None) -> Any:
    """Family-shaped cache dict; attention entries are ``KVCache``s.

    mode: init (arrays) | abstract (ShapeDtypeStruct). ``kv_impl``
    selects the self-attention cache layout ("dense" | "paged");
    ``kv_block``/``kv_blocks`` size the paged pool (``kv_blocks=None``
    defaults to dense-equivalent capacity).
    """
    if mode not in ("init", "abstract"):
        raise ValueError(f"make_cache mode {mode!r}")
    abstract = mode == "abstract"
    fam = cfg.family

    def attn(n_layers):
        return kvc.make_kv_cache(cfg, n_layers, batch, max_len,
                                 impl=kv_impl, block=kv_block,
                                 n_blocks=kv_blocks, abstract=abstract)

    if fam in ("dense", "moe", "vlm"):
        return {"attn": attn(cfg.n_layers)}
    if fam == "ssm":
        return {"ssm": _ssm_struct(cfg, batch, mode)}
    if fam == "hybrid":
        return {"attn": attn(_n_shared_apps(cfg)),
                "ssm": _ssm_struct(cfg, batch, mode)}
    if fam == "audio":
        return {"self": attn(cfg.n_layers),
                "cross": _cross_struct(cfg, batch, mode)}
    raise ValueError(fam)


def cache_shardings(cfg: ModelConfig, rules, mesh=None, *,
                    batch_sharded: bool = True, cache: Any = None,
                    row_axis: Optional[str] = "__default__") -> Any:
    """NamedShardings matching a ``make_cache`` tree under ``rules``.

    ``batch_sharded=False`` replicates the per-row dim (callers whose
    serving batch does not divide the data axes, e.g. dry-run cells).
    ``cache`` (optional) is an existing cache tree — real or abstract —
    to mirror; required when it is not the dense default. ``row_axis``
    overrides the logical axis the per-row dim maps to (the scheduler
    passes ``SLOT``); the default derives it from ``batch_sharded``.
    """
    if cache is None:
        cache = make_cache(cfg, 0, 0, mode="abstract")
    if row_axis == "__default__":
        row_axis = sh.BATCH if batch_sharded else None

    def fix(spec, leaf):
        spec = tuple(row_axis if a == sh.BATCH else a for a in spec)
        return rules.sharding(spec, mesh, dims=leaf.shape)

    out = {}
    for key, node in cache.items():
        if isinstance(node, kvc.KVCache):
            out[key] = node.shardings(rules, mesh, row_axis=row_axis)
        else:
            axes = (_ssm_struct if key == "ssm" else _cross_struct)(
                cfg, 0, "axes")
            out[key] = jax.tree.map(fix, axes, node,
                                    is_leaf=lambda x: isinstance(x, tuple))
    return out


# =========================== logits head ====================================

def _logits_head(params, cfg: ModelConfig, x: jax.Array, rules):
    """Final norm + (tied / untied) unembed — the shared tail of
    ``prefill``, ``prefill_chunk``, and ``decode_step``."""
    cdt = cfg.dtype("compute")
    if cfg.family == "audio":
        x = layers.layer_norm(x, params["ln_final"], params["ln_final_b"])
        w = params["embed"].astype(cdt).T
    else:
        x = layers.apply_norm(cfg.norm, x, params, "ln_final", cfg.norm_eps)
        w = (params["embed"].T if cfg.tie_embeddings
             else params["unembed"]).astype(cdt)
    logits = jnp.einsum("bsd,dv->bsv", x.astype(cdt), w)
    return sh.constrain(logits, rules, (sh.BATCH, None, sh.VOCAB))


# =========================== decode steps ===================================

def _decode_positions(cur_len):
    """(1, 1) positions for a scalar ``cur_len``; (B, 1) for a vector.

    A vector means per-row sequence depths: slot-based continuous
    batching (``repro.serve.scheduler``) decodes a pool of sequences
    that each sit at a different position.
    """
    cl = jnp.asarray(cur_len)
    if cl.ndim == 0:
        return jnp.full((1, 1), cl - 1, jnp.int32)
    return (cl - 1).astype(jnp.int32)[:, None]


def _attn_scan(params, cfg, rules, x, node, *, view, valid=None,
               **attn_kw):
    """The attention-family layer scan: ``attn_block`` at every layer,
    with the FFN ``transformer.layer_ffn`` gives it, over ``node``'s
    views (``view``: their ``rows``/``mask``). Returns (x, the cache
    node with the new leaves, the layers' counts summed, as
    ``transformer.zero_counts`` names them: ``{}`` for most models)."""
    def f(carry, xs):
        xx, cnt = carry
        lp, lv, i = xs
        xx, new_view, c = transformer.attn_block(
            lp, xx, cfg, rules, kv_cache=node.view(lv, **view), valid=valid,
            ffn=transformer.layer_ffn(params, cfg, i, valid), **attn_kw)
        return (xx, {k: cnt[k] + c[k] for k in cnt}), new_view.leaves

    idx = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    (x, counts), new_leaves = jax.lax.scan(
        f, (x, transformer.zero_counts(cfg)),
        (params["layers"], node.layers, idx))
    return x, node.with_layers(new_leaves), counts


def _decode_attn_families(params, cfg, rules, x, cache, cur_len,
                          write_mask=None, live=None):
    positions = _decode_positions(cur_len)
    # Copy-on-write BEFORE the layer scan: the append at cur_len - 1
    # must never land in a block other references still read (prefix
    # sharing). Table/refcount are cross-layer state, so this runs once
    # per step, not per layer; a no-op for dense and unshared pools.
    node = cache["attn"].ensure_private(
        start=jnp.asarray(cur_len, jnp.int32) - 1, width=1,
        mask=write_mask)
    if transformer.dropless(cfg):
        # dropless MoE: rows that do not emit are not real tokens
        x, node, counts = _attn_scan(
            params, cfg, rules, x, node, view={"mask": write_mask},
            valid=None if write_mask is None else write_mask[:, None],
            positions=positions, mode="decode", cur_len=cur_len)
        depth = jnp.full((x.shape[0],), cfg.n_layers, jnp.int32)
        return x, {"attn": node}, depth, counts

    def block_fn(lp, lv, xx, i):
        x2, new_view, _ = transformer.attn_block(
            lp, xx, cfg, rules, positions=positions, mode="decode",
            kv_cache=node.view(lv, mask=write_mask), cur_len=cur_len)
        if adaptive.mod_on(cfg):
            x2, applied = adaptive.mod_apply_decode(lp["router"], xx, x2,
                                                    i, cfg)
        else:
            applied = jnp.ones((xx.shape[0],), bool)
        return x2, new_view.leaves, applied

    halt_fn = adaptive.make_halt_fn(params, cfg)
    kv_fill_fn = None
    if halt_fn is not None:
        # Skipped-layer KV propagation: project the frozen hidden state
        # into every remaining layer's cache — no q / attention / MLP.
        def kv_fill_fn(lp, lv, xx, i):
            h = layers.apply_norm(cfg.norm, xx, lp, "ln_attn")
            new_view = transformer.kv_project_append(
                lp["attn"], h, cfg, node.view(lv, mask=write_mask),
                positions, cur_len)
            return new_view.leaves

    x, new_leaves, depth = transformer.decode_layers(
        params["layers"], x, node.layers, cfg, block_fn=block_fn,
        halt_fn=halt_fn, kv_fill_fn=kv_fill_fn, live=live)
    return x, {"attn": node.with_layers(new_leaves)}, depth, {}


def _decode_ssm(params, cfg, rules, x, cache, cur_len):
    def f(carry, xs):
        x = carry
        lp, st = xs
        x, new_st = transformer.ssm_block(lp, x, cfg, rules, mode="decode",
                                          state=st)
        return x, new_st

    x, new_ssm = jax.lax.scan(f, x, (params["layers"], cache["ssm"]))
    return x, {"ssm": new_ssm}


def _decode_hybrid(params, cfg, rules, x, cache, cur_len):
    k = cfg.shared_attn_every
    L = cfg.n_layers
    positions = _decode_positions(cur_len)
    node = cache["attn"].ensure_private(
        start=jnp.asarray(cur_len, jnp.int32) - 1, width=1)
    new_ssm = cache["ssm"]
    for app, start in enumerate(range(0, L, k)):
        x, new_view, _ = transformer.attn_block(
            params["shared_attn"], x, cfg, rules, positions=positions,
            mode="decode", kv_cache=node.view_at(app), cur_len=cur_len)
        node = node.set_at(app, new_view)
        stop = min(start + k, L)
        seg_p = jax.tree.map(lambda a: a[start:stop], params["layers"])
        seg_s = jax.tree.map(lambda a: a[start:stop], cache["ssm"])

        def f(carry, xs):
            x = carry
            lp, st = xs
            x, new_st = transformer.ssm_block(lp, x, cfg, rules,
                                              mode="decode", state=st)
            return x, new_st

        x, seg_new = jax.lax.scan(f, x, (seg_p, seg_s))
        new_ssm = jax.tree.map(
            lambda full, n: jax.lax.dynamic_update_slice_in_dim(
                full, n.astype(full.dtype), start, axis=0),
            new_ssm, seg_new)
    return x, {"attn": node, "ssm": new_ssm}


def _decode_audio(params, cfg, rules, x, cache, cur_len):
    node = cache["self"].ensure_private(
        start=jnp.asarray(cur_len, jnp.int32) - 1, width=1)

    def f(carry, xs):
        x = carry
        lp, leaves, cross = xs
        x, new_view = encdec._dec_block(
            lp, x, cfg, rules, mode="decode", self_kv=node.view(leaves),
            cross_kv=kvc.DenseView(cross["k"], cross["v"]), cur_len=cur_len)
        return x, new_view.leaves

    x, new_leaves = jax.lax.scan(
        f, x, (params["decoder"], node.layers, cache["cross"]))
    return x, {"self": node.with_layers(new_leaves),
               "cross": cache["cross"]}


def decode_step(params, cfg: ModelConfig, token: jax.Array, cache: Any,
                cur_len, rules=None, *, write_mask=None, live=None,
                with_depth: bool = False, with_counts: bool = False):
    """One new token against a cache of `cur_len - 1` previous positions.

    token: (B, 1) int32. ``cur_len`` is a scalar (whole batch at the
    same depth — the batch-synchronous loop) or a (B,) vector of
    per-row depths (slot-based continuous batching). Returns
    (logits (B, 1, Vp), new_cache) — or (logits, new_cache, depth)
    with ``with_depth=True``, where depth (B,) int32 counts decoder
    blocks actually applied per row this step (== n_layers unless
    adaptive depth is active; see ``models.adaptive``).

    ``write_mask`` (optional, attention families only): (B,) bool —
    rows whose K/V append should actually land. The chunked-prefill
    scheduler decodes the whole pool every step while some slots are
    still mid-prefill; those slots' garbage appends must NOT land at
    ``cur_len - 1`` (that is prompt position 0 they already wrote), so
    the decode write is gated where the one-shot scheduler could rely
    on retired rows being rewritten at admission.

    ``live`` (optional, adaptive early-exit only): (B,) bool — rows
    whose halt bit should keep the dynamic layer loop alive. Retired /
    mid-prefill slots pass False: they start halted, pay no block
    FLOPs, and never extend the loop. None = every row live.

    ``with_counts=True`` appends the step's layer counts
    (``transformer.zero_counts``: a dropless MoE's routed and local
    entries of the emitting rows, summed over layers; ``{}`` else).
    """
    cdt = cfg.dtype("compute")
    x = jnp.take(params["embed"].astype(cdt), token, axis=0)
    x = sh.constrain(x, rules, (sh.BATCH, None, None))
    fam = cfg.family
    counts = {}
    if write_mask is not None and fam not in ("dense", "moe", "vlm"):
        raise ValueError(f"write_mask is only supported for attention "
                         f"families; got family {fam!r}")
    if adaptive.enabled(cfg):
        if cfg.mla is not None or cfg.first_k_dense:
            raise ValueError("adaptive depth does not serve latent "
                             "attention or split FFN stacks")
        adaptive.validate(cfg)
    elif live is not None:
        raise ValueError("live= requires adaptive depth "
                         "(cfg.early_exit / cfg.mod_capacity)")
    if fam in ("dense", "moe", "vlm"):
        x, new_cache, depth, counts = _decode_attn_families(
            params, cfg, rules, x, cache, cur_len, write_mask, live=live)
    elif fam == "ssm":
        x, new_cache = _decode_ssm(params, cfg, rules, x, cache, cur_len)
        depth = jnp.full((x.shape[0],), cfg.n_layers, jnp.int32)
    elif fam == "hybrid":
        x, new_cache = _decode_hybrid(params, cfg, rules, x, cache, cur_len)
        depth = jnp.full((x.shape[0],), cfg.n_layers, jnp.int32)
    elif fam == "audio":
        pe = layers.sinusoid_at(jnp.asarray(cur_len) - 1, cfg.d_model, cdt)
        x = x + (pe if pe.ndim == 1 else pe[:, None, :])
        x, new_cache = _decode_audio(params, cfg, rules, x, cache, cur_len)
        depth = jnp.full((x.shape[0],), cfg.n_layers, jnp.int32)
    else:
        raise ValueError(fam)

    logits = _logits_head(params, cfg, x, rules)
    out = (logits, new_cache)
    if with_depth:
        out += (depth,)
    if with_counts:
        out += (counts,)
    return out


def verify_step(params, cfg: ModelConfig, tokens: jax.Array, cache: Any,
                cur_len, rules=None, *, write_mask=None
                ) -> Tuple[jax.Array, Any]:
    """Score a W-token speculative window in ONE forward.

    tokens: (B, W) int32 — ``[pending, d_1..d_{W-1}]`` per row; the
    window starts at ``cur_len - 1`` (the pending token's position), so
    position ``j``'s logits are the distribution over the token at
    emission index ``n_emitted + j + 1`` GIVEN the window prefix up to
    ``j``. Returns (logits (B, W, Vp), new_cache).

    The window's K/V is written through the chunked-prefill write path
    at per-row offsets (``mode="verify"``: ``write_chunk`` then
    decode-exact ``verify_attention``), overwriting any stale
    rejected-draft lanes from the previous iteration before a query
    can see them. Attention-decoder families only — the same gate as
    chunked prefill, whose machinery this rides. ``write_mask`` gates
    rows exactly as in ``decode_step``.
    """
    fam = cfg.family
    if fam not in ("dense", "moe", "vlm") or cfg.mla is not None:
        raise ValueError(f"verify_step requires an attention-decoder "
                         f"family (dense/moe/vlm) without latent "
                         f"attention; got {cfg.name!r}")
    cdt = cfg.dtype("compute")
    W = tokens.shape[1]
    off = jnp.asarray(cur_len, jnp.int32) - 1                   # (B,)
    positions = off[:, None] + jnp.arange(W, dtype=jnp.int32)[None]
    x = jnp.take(params["embed"].astype(cdt), tokens, axis=0)
    x = sh.constrain(x, rules, (sh.BATCH, None, None))
    # CoW before the layer scan, once per window (cross-layer state) —
    # rejected drafts therefore can never write into a block other
    # references still read, the §8.3 sharing invariant.
    node = cache["attn"].ensure_private(start=off, width=W,
                                        mask=write_mask)

    def f(carry, xs):
        x = carry
        lp, leaves = xs
        x, new_view, _ = transformer.attn_block(
            lp, x, cfg, rules, positions=positions, mode="verify",
            kv_cache=node.view(leaves, mask=write_mask), chunk_off=off)
        return x, new_view.leaves

    x, new_leaves = jax.lax.scan(f, x, (params["layers"], node.layers))
    return (_logits_head(params, cfg, x, rules),
            {"attn": node.with_layers(new_leaves)})


# =========================== prefill ========================================

def prefill(params, cfg: ModelConfig, tokens: jax.Array, cache: Any,
            rules=None, prefix_embeds=None, frames=None, *,
            rows=None, mask=None) -> Tuple[jax.Array, Any]:
    """Prime the cache with a full prompt; returns (logits, new_cache).

    ``rows``/``mask`` (optional) bind prompt-batch row ``i`` to cache
    row ``rows[i]``, writing only masked rows — the scheduler's
    prefill-into-slot admission. Attention ``KVCache`` entries are
    written **in place at those rows** (a no-op for unmasked rows); SSM
    and audio-cross entries are returned as FRESH prompt-batch-wide
    state — the in-graph admission splices those along their batch
    axis. With ``rows=None`` (batch-synchronous path) prompt row b is
    cache row b and every entry lines up dense.
    """
    cdt = cfg.dtype("compute")
    fam = cfg.family
    x = jnp.take(params["embed"].astype(cdt), tokens, axis=0)
    if fam == "vlm" and prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(cdt), x], axis=1)
    x = sh.constrain(x, rules, (sh.BATCH, None, None))
    S = x.shape[1]
    positions = jnp.arange(S)[None]

    if fam in ("dense", "moe", "vlm"):
        # CoW before the prompt write sweep (no-op unless shared)
        node = cache["attn"].ensure_private(rows, start=0, width=S,
                                            mask=mask)
        x, node, _ = _attn_scan(params, cfg, rules, x, node,
                                view={"rows": rows, "mask": mask},
                                positions=positions, mode="prefill")
        new_cache = {"attn": node}
    elif fam == "ssm":
        def f(carry, lp):
            x = carry
            h = layers.apply_norm(cfg.norm, x, lp, "ln")
            fwd = (ssm_lib.mamba1_forward if cfg.ssm.kind == "mamba1"
                   else ssm_lib.mamba2_forward)
            y, st = fwd(lp["ssm"], h, cfg, rules, return_state=True)
            return x + y, st
        x, new_ssm = jax.lax.scan(f, x, params["layers"])
        new_cache = {"ssm": new_ssm}
    elif fam == "hybrid":
        k = cfg.shared_attn_every
        L = cfg.n_layers
        node = cache["attn"].ensure_private(rows, start=0, width=S,
                                            mask=mask)
        new_ssm = cache["ssm"]
        for app, start in enumerate(range(0, L, k)):
            x, new_view, _ = transformer.attn_block(
                params["shared_attn"], x, cfg, rules, positions=positions,
                mode="prefill", kv_cache=node.view_at(app, rows=rows,
                                                      mask=mask))
            node = node.set_at(app, new_view)
            stop = min(start + k, L)
            seg_p = jax.tree.map(lambda a: a[start:stop], params["layers"])

            def f(carry, lp):
                x = carry
                h = layers.apply_norm(cfg.norm, x, lp, "ln")
                y, st = ssm_lib.mamba2_forward(lp["ssm"], h, cfg, rules,
                                               return_state=True)
                return x + y, st
            x, seg_new = jax.lax.scan(f, x, seg_p)
            new_ssm = jax.tree.map(
                lambda full, n: jax.lax.dynamic_update_slice_in_dim(
                    full, n.astype(full.dtype), start, axis=0),
                new_ssm, seg_new)
        new_cache = {"attn": node, "ssm": new_ssm}
    elif fam == "audio":
        enc_out = encdec.encode(params, cfg, frames, rules)
        cross = encdec.cross_kv(params, cfg, enc_out)
        x = x + layers.sinusoidal_positions(S, cfg.d_model, cdt)
        node = cache["self"].ensure_private(rows, start=0, width=S,
                                            mask=mask)

        def f(carry, xs):
            x = carry
            lp, leaves = xs
            x, new_view = encdec._dec_block(
                lp, x, cfg, rules, enc_out, mode="prefill",
                self_kv=node.view(leaves, rows=rows, mask=mask))
            return x, new_view.leaves
        x, new_leaves = jax.lax.scan(f, x, (params["decoder"], node.layers))
        new_cache = {"self": node.with_layers(new_leaves), "cross": cross}
    else:
        raise ValueError(fam)

    return _logits_head(params, cfg, x, rules), new_cache


def prefill_chunk(params, cfg: ModelConfig, prompts: jax.Array, cache: Any,
                  offsets, rules=None, *, chunk: int, mask=None,
                  prefix_embeds=None, valid=None,
                  with_counts: bool = False):
    """Advance prefill by one ``chunk``-token slice of the prompt stream.

    The chunked-prefill step (DESIGN.md §8.2): instead of priming the
    cache with one monolithic prompt forward, the scheduler calls this
    repeatedly — each call embeds STREAM positions
    ``[offsets[i], offsets[i] + chunk)`` of row ``i`` (the stream is
    the VLM patch prefix followed by the prompt tokens), writes their
    K/V into the cache at those offsets (``view.write_chunk``), and
    attends causally against everything already written — through the
    block table (``kernels.flash_prefill``) when
    ``cfg.attn_impl == "pallas"`` and the cache is paged, so no dense
    ``(rows, max_len, KV, hd)`` intermediate is ever materialized.

    prompts: (n, W) int32 — the FULL per-row token buffers (rows
    right-padded with anything; lanes past a row's true length are
    garbage whose K/V is causally invisible to real queries, the same
    argument that makes right-padded one-shot prefill exact).
    offsets: (n,) int32 per-row stream offsets; ``mask`` (n,) bool
    selects the rows actually advancing (unmasked rows compute garbage
    and write nothing). ``prefix_embeds`` (n, n_patches, d) feeds the
    VLM patch prefix at stream positions ``[0, n_patches)``.

    Returns (logits (n, chunk, Vp), new_cache): each row's token-0
    sample reads ``logits[i, plen - 1 - offsets[i]]`` from the call
    whose window contains its last real position.

    ``valid`` (n, chunk) bool marks the real prompt positions (default:
    every position of a masked row): a dropless MoE layer computes
    only their entries. ``with_counts=True`` appends the layer counts,
    as ``decode_step``'s.
    """
    cdt = cfg.dtype("compute")
    fam = cfg.family
    if fam in ("ssm", "hybrid"):
        raise ValueError(
            f"chunked prefill requires an attention-family cache; family "
            f"{fam!r} folds its recurrent state through a full-prompt "
            f"forward (use engine.prefill)")
    n, W = prompts.shape
    C = int(chunk)
    offsets = jnp.asarray(offsets, jnp.int32)
    pos = offsets[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    prefix = 0
    if fam == "vlm" and prefix_embeds is not None:
        prefix = cfg.n_patches
    tid = jnp.take_along_axis(prompts, jnp.clip(pos - prefix, 0, W - 1),
                              axis=1)
    x = jnp.take(params["embed"].astype(cdt), tid, axis=0)
    if prefix:
        pe = jnp.take_along_axis(
            prefix_embeds.astype(cdt),
            jnp.clip(pos, 0, prefix - 1)[..., None], axis=1)
        x = jnp.where((pos < prefix)[..., None], pe, x)
    if fam == "audio":
        x = x + layers.sinusoid_at(pos, cfg.d_model, cdt)
    x = sh.constrain(x, rules, (sh.BATCH, None, None))

    counts = {}
    if fam in ("dense", "moe", "vlm"):
        # CoW before the chunk write: a prefix-cache row's first
        # uncached chunk must not scribble over a shared block (the
        # scheduler's block-aligned sharing cap makes this a no-op in
        # practice; it is the safety invariant)
        node = cache["attn"].ensure_private(start=offsets, width=C,
                                            mask=mask)
        if valid is None and mask is not None:
            valid = jnp.broadcast_to(jnp.asarray(mask)[:, None], (n, C))
        x, node, counts = _attn_scan(
            params, cfg, rules, x, node, view={"mask": mask}, valid=valid,
            positions=pos, mode="chunk", chunk_off=offsets)
        new_cache = {"attn": node}
    else:   # audio: cross cache must already be primed (written once
            # per request at its fixed n_frames width)
        node = cache["self"].ensure_private(start=offsets, width=C,
                                            mask=mask)

        def f(carry, xs):
            x = carry
            lp, leaves, cross = xs
            x, new_view = encdec._dec_block(
                lp, x, cfg, rules, mode="chunk",
                self_kv=node.view(leaves, mask=mask),
                cross_kv=kvc.DenseView(cross["k"], cross["v"]),
                chunk_off=offsets)
            return x, new_view.leaves
        x, new_leaves = jax.lax.scan(
            f, x, (params["decoder"], node.layers, cache["cross"]))
        new_cache = {"self": node.with_layers(new_leaves),
                     "cross": cache["cross"]}

    logits = _logits_head(params, cfg, x, rules)
    if with_counts:
        return logits, new_cache, counts
    return logits, new_cache


# =========================== in-graph generation ============================

def resolved_attn_impl(cfg: ModelConfig, kv_impl: str) -> str:
    """Which decode-attention path a (cfg, kv_impl) pair actually runs.

    The Pallas paged-attention kernel engages only when BOTH the
    config asks for it (``cfg.attn_impl == "pallas"``) and the cache
    view is paged; off TPU the kernel runs in interpret mode — a
    correctness path, NOT a fast path — and benchmark readers must be
    able to tell (a CPU "pallas" number silently read as a TPU number
    is exactly the confusion this string exists to prevent).
    Attention-free families (pure SSM) have no KV cache and no
    attention path at all, whatever the knobs say.
    """
    if kv_key(cfg) is None:
        return "attention-free"
    if cfg.attn_impl == "pallas" and kv_impl == "paged":
        from ..kernels import on_tpu
        return "pallas-paged:" + ("compiled" if on_tpu() else "interpret")
    return f"xla-gather:{kv_impl}"


def resolved_prefill_impl(cfg: ModelConfig, kv_impl: str,
                          prefill: str = "oneshot") -> str:
    """Which PREFILL attention path a (cfg, kv_impl, prefill) triple
    actually runs — the prefill-side twin of ``resolved_attn_impl``.

    "dense-bucketed" is the one-shot path: admission computes
    attention over the dense (right-padded / bucketed) prompt q/k/v,
    whatever the KV layout. "flash-paged:*" is chunked prefill
    streaming prior K/V through the block table
    (``kernels.flash_prefill``) — ``:interpret`` off TPU, a
    correctness path whose timings must never be read as TPU numbers.
    "xla-chunked" is chunked prefill on the gather fallback. Pure-SSM
    families have no attention prefill at all.
    """
    if kv_key(cfg) is None:
        return "attention-free"
    if prefill == "chunked":
        if cfg.attn_impl == "pallas" and kv_impl == "paged":
            from ..kernels import on_tpu
            return "flash-paged:" + ("compiled" if on_tpu()
                                     else "interpret")
        return "xla-chunked"
    return "dense-bucketed"


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class GenerateResult:
    """Per-request generation output.

    ``lengths`` **counts the EOS token**: a row that produced 3 text
    tokens and then EOS has ``lengths == 4`` (``tokens[b, :lengths[b]]``
    is the full emission, EOS included). ``text_lengths`` is the number
    of tokens *before* EOS — what callers previously re-derived by
    hand. A row that never hit EOS has
    ``lengths == text_lengths == max_new``.

    ``attn_impl`` reports the decode-attention path that actually ran
    (``resolved_attn_impl``): "xla-gather:dense", "xla-gather:paged",
    "pallas-paged:compiled", "pallas-paged:interpret", or
    "attention-free" (pure-SSM families); ``prefill_impl`` reports the
    PREFILL path the same way (``resolved_prefill_impl``):
    "dense-bucketed", "flash-paged:compiled", "flash-paged:interpret",
    "xla-chunked", or "attention-free" — so interleaved-mode CPU
    interpret numbers can't be misread as TPU numbers. Both are static
    metadata (pytree aux), so jitted callers carry them for free.

    ``transfer_impl`` reports how prefilled KV reached the decode
    kernel: "colocated" (same pool — every single-tier path), or
    "device_put:ics" / "device_put:dcn" for a disaggregated run whose
    prefill-slice blocks shipped within one process / across processes
    (``repro.serve.disagg``) — so disagg benchmark numbers can't be
    misread as colocated ones (or vice versa).
    """

    tokens: jax.Array        # (B, max_new)
    lengths: jax.Array       # (B,) emitted tokens, EOS included
    steps: jax.Array         # scalar: loop iterations actually run
    text_lengths: jax.Array  # (B,) tokens before EOS
    attn_impl: str = ""      # resolved decode-attention path (static)
    prefill_impl: str = ""   # resolved prefill-attention path (static)
    transfer_impl: str = ""  # prefill→decode KV transfer path (static)

    def tree_flatten(self):
        return (self.tokens, self.lengths, self.steps,
                self.text_lengths), (self.attn_impl, self.prefill_impl,
                                     self.transfer_impl)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, attn_impl=aux[0], prefill_impl=aux[1],
                   transfer_impl=aux[2])


def _result_from_tokens(toks, eos_id, steps, attn_impl: str = "",
                        prefill_impl: str = "",
                        transfer_impl: str = "") -> "GenerateResult":
    has_eos = (toks == eos_id).any(axis=1)
    first_eos = jnp.argmax(toks == eos_id, axis=1)
    lengths = jnp.where(has_eos, first_eos + 1, toks.shape[1])
    return GenerateResult(tokens=toks, lengths=lengths,
                          steps=jnp.asarray(steps, jnp.int32),
                          text_lengths=lengths - has_eos,
                          attn_impl=attn_impl, prefill_impl=prefill_impl,
                          transfer_impl=transfer_impl)


def generate_batch_sync(params, cfg: ModelConfig, prompt: jax.Array, *,
                        max_new: int, eos_id: int = 1, rules=None,
                        prefix_embeds=None, frames=None,
                        kv_impl: str = "dense", kv_block: int = 16
                        ) -> GenerateResult:
    """Greedy in-graph decode with EOS early exit (dynamic control flow).

    The whole loop is one ``repro.core.while_loop``: the predicate is
    data-dependent (all sequences hit EOS → exit early), which is
    impossible with a fixed-length ``lax.scan`` — exactly the paper's
    argument for in-graph dynamic control flow in inference.

    This is the **batch-synchronous** path: the batch is admitted as a
    whole and the call returns when the slowest sequence finishes, so a
    freed row idles until the entire batch drains. It remains the
    jittable reference implementation; traffic serving should use
    ``repro.serve.scheduler`` (continuous batching), which ``generate``
    wraps. ``kv_impl`` selects the cache layout — "paged" runs the
    block-table cache at dense-equivalent capacity, which the
    equivalence tests use to pin bit-identical greedy tokens.
    """
    B, S = prompt.shape
    prefix = cfg.n_patches if (cfg.family == "vlm"
                               and prefix_embeds is not None) else 0
    max_len = S + prefix + max_new + 1
    cache = make_cache(cfg, B, max_len, kv_impl=kv_impl, kv_block=kv_block)
    key = kv_key(cfg)
    if key is not None:
        # Batch-sync admits every row up front with the full budget.
        cache[key] = cache[key].alloc(jnp.arange(B, dtype=jnp.int32),
                                      jnp.full((B,), max_len, jnp.int32))
    logits, cache = prefill(params, cfg, prompt, cache, rules,
                            prefix_embeds=prefix_embeds, frames=frames)
    first = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    out_ta = core.TensorArray.create(max_new, (B,), jnp.int32)
    done0 = jnp.zeros((B,), bool)
    cur0 = jnp.asarray(S + prefix + 1, jnp.int32)

    def cond_fn(state):
        i, token, done, cur, cache, ta = state
        return jnp.logical_not(jnp.all(done))

    def body_fn(state):
        i, token, done, cur, cache, ta = state
        ta = ta.write(i, jnp.where(done, eos_id, token[:, 0]))
        done = done | (token[:, 0] == eos_id)
        # EOS-finished rows start the adaptive layer loop halted: they
        # stop paying per-layer FLOPs as well as being masked at emit.
        logits, cache = decode_step(
            params, cfg, token, cache, cur, rules,
            live=~done if cfg.early_exit else None)
        nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        return (i + 1, nxt, done, cur + 1, cache, ta)

    i, _, done, _, _, ta = core.while_loop(
        cond_fn, body_fn, (jnp.asarray(0, jnp.int32), first, done0, cur0,
                           cache, out_ta),
        max_iters=max_new, name="generate")
    toks = ta.stack().T                                  # (B, max_new)
    return _result_from_tokens(
        toks, eos_id, i, attn_impl=resolved_attn_impl(cfg, kv_impl),
        prefill_impl=resolved_prefill_impl(cfg, kv_impl, "oneshot"),
        transfer_impl="colocated")


# Wrapper scheduler reuse: jit caches key on closure identity, so a
# fresh DecodeScheduler per generate() call would recompile the model
# every time. Schedulers are cached on the static call signature; each
# cached scheduler holds its cfg/rules refs, keeping their id()s alive
# and therefore unambiguous as keys.
_WRAPPER_SCHEDULERS: "collections.OrderedDict" = collections.OrderedDict()
_WRAPPER_CACHE_SIZE = 8


def clear_generate_cache() -> None:
    """Drop the wrapper's cached schedulers (device cache pools + the
    params they reference). Call when done generating to return that
    memory to the allocator — e.g. before switching to training."""
    _WRAPPER_SCHEDULERS.clear()


def generate(params, cfg: ModelConfig, prompt: jax.Array, *, max_new: int,
             eos_id: int = 1, rules=None, prefix_embeds=None, frames=None,
             kv_impl: str = "dense", kv_block: int = 16) -> GenerateResult:
    """Greedy decode for a batch of prompts (compatibility wrapper).

    Thin wrapper over the slot-based continuous-batching scheduler
    (``repro.serve.scheduler``): every prompt is submitted as its own
    request into a pool of ``B`` slots and the pool drains. Per-request
    greedy tokens are bit-identical to ``generate_batch_sync`` for the
    row-independent families — a sequence's tokens do not depend on
    what else shares the pool (tested in
    ``tests/serve/test_scheduler.py``). The exception is ``moe``:
    capacity-limited routing groups the whole decode batch, so retired
    rows' frozen feed tokens can shift the surviving rows' expert
    assignments relative to the batch-synchronous loop (whose done
    rows keep evolving) — the same batch-composition coupling MoE
    decode already has inside one batch. Host-driven (admission
    happens between device steps), so NOT jittable — jit the
    scheduler's step function instead, or use ``generate_batch_sync``
    for a fully in-graph loop.

    Repeat calls with the same (cfg, rules, shapes) reuse a cached
    scheduler (compiled traces + device cache pool); the cache holds up
    to ``_WRAPPER_CACHE_SIZE`` pools alive — ``clear_generate_cache()``
    releases them.
    """
    from . import scheduler as sched_lib  # deferred: scheduler imports us

    B, S = prompt.shape
    prefix = cfg.n_patches if (cfg.family == "vlm"
                               and prefix_embeds is not None) else 0
    key = (id(cfg), id(rules), B, S, max_new, int(eos_id), prefix,
           frames is not None, kv_impl, kv_block)
    sched = _WRAPPER_SCHEDULERS.get(key)
    if sched is None:
        sched = sched_lib.DecodeScheduler(
            params, cfg, n_slots=B, prompt_len=S, max_new_cap=max_new,
            eos_id=eos_id, rules=rules, prefix_len=prefix,
            kv=kv_impl, kv_block=kv_block)
        _WRAPPER_SCHEDULERS[key] = sched
        while len(_WRAPPER_SCHEDULERS) > _WRAPPER_CACHE_SIZE:
            _WRAPPER_SCHEDULERS.popitem(last=False)
    else:
        _WRAPPER_SCHEDULERS.move_to_end(key)
        sched.params = params   # fresh weights reuse the cached traces
    prompt_np = np.asarray(prompt)   # one transfer, sliced host-side
    for b in range(B):
        sched.submit(
            prompt_np[b:b + 1], max_new=max_new, request_id=b,
            prefix_embeds=(None if prefix_embeds is None
                           else prefix_embeds[b:b + 1]),
            frames=None if frames is None else frames[b:b + 1])
    finished = sched.run_until_drained()
    toks = np.full((B, max_new), eos_id, dtype=np.int32)
    for f in finished:
        toks[f.request_id, :f.length] = f.tokens
    # run_until_drained resets stats at entry (idle pool), so
    # total_steps already counts exactly this run's iterations
    return _result_from_tokens(jnp.asarray(toks), eos_id,
                               sched.total_steps,
                               attn_impl=sched.attn_impl,
                               prefill_impl=sched.prefill_impl,
                               transfer_impl=sched.transfer_impl)
