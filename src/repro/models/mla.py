"""Multi-head latent attention (DeepSeek-V2/V3; here without a q-LoRA).

Per token, one latent row is cached for all heads: ``c`` (the
``kv_lora_rank`` values of ``x W_kv_a``, RMS-normed) and one shared
RoPE key ``k_pe``. Head ``h``'s key is ``[c W_UK_h, k_pe]`` and its
value ``c W_UV_h``; the softmax scale is ``1/sqrt(nope + rope)``.

Two forms of the same attention:

- **expanded** (``mode`` full / prefill): K and V of every head are
  computed from ``c`` and attention runs per head, as published;
- **absorbed** (``mode`` chunk / decode, reading the cache): ``W_UK``
  is folded into the query, ``q_abs = q_nope W_UK^T``, so each head's
  query ``[q_abs, q_pe]`` scores the cached rows ``[c, k_pe]``
  directly, and the attention-weighted rows' ``c`` part is taken
  through ``W_UV``. The cache is read as one shared head of
  ``row_lanes`` values whose value is its own first ``kv_lora_rank``
  lanes: through the block table by the Pallas decode and
  chunk-prefill kernels under ``attn_impl="pallas"`` and a paged
  pool, else by a gather.

A row is stored as ``c ‖ k_pe`` zero-padded to whole 128-lane tiles
(``MLAConfig.row_lanes``); the zero lanes add nothing to a score.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..dist import sharding as sh
from . import attention as attn_lib
from . import layers

NEG_INF = -1e30


def mla_params(b, cfg, d_model: int):
    a, H = cfg.mla, cfg.n_heads
    return {
        "wq": b.p((d_model, H, a.qk_head_dim),
                  (sh.EMBED, sh.HEADS, sh.HEAD_DIM), fan_in=d_model),
        "wkv_a": b.p((d_model, a.row), (sh.EMBED, None), fan_in=d_model),
        "kv_norm": b.p((a.kv_lora_rank,), (None,), init="ones"),
        "wk_b": b.p((a.kv_lora_rank, H, a.qk_nope_head_dim),
                    (None, sh.HEADS, sh.HEAD_DIM), fan_in=a.kv_lora_rank),
        "wv_b": b.p((a.kv_lora_rank, H, a.v_head_dim),
                    (None, sh.HEADS, sh.HEAD_DIM), fan_in=a.kv_lora_rank),
        "wo": b.p((H, a.v_head_dim, d_model),
                  (sh.HEADS, sh.HEAD_DIM, sh.EMBED),
                  fan_in=H * a.v_head_dim),
    }


def scale(cfg) -> float:
    return 1.0 / math.sqrt(cfg.mla.qk_head_dim)


def latent_attention(q, kv, *, q_off, cfg, decode: bool):
    """Absorbed attention of ``q`` (B, S, H, lanes) over a latent cache
    view: query ``i`` of row ``b`` sees positions ``[0, q_off[b] + i]``.
    Returns the attention-weighted rows (B, S, H, lanes) in q's dtype;
    their first ``kv_lora_rank`` lanes are the latent value."""
    s = scale(cfg)
    q_off = jnp.broadcast_to(jnp.asarray(q_off, jnp.int32), (q.shape[0],))
    if cfg.attn_impl == "pallas":
        state = getattr(kv, "paged_state", lambda: None)()
        if state is not None:
            pool, _, table = state
            if decode:
                from ..kernels.paged_attention.ops import paged_attention
                return paged_attention(q, pool, None, table, q_off + 1,
                                       scale=s)
            from ..kernels.flash_prefill.ops import flash_prefill
            return flash_prefill(q, pool, None, table, q_off, scale=s)
    rows = kv.gather()[0][:, :, 0].astype(jnp.float32)       # (B, T, lanes)
    S, T = q.shape[1], rows.shape[1]
    sc = jnp.einsum("bshc,btc->bsht", q.astype(jnp.float32), rows) * s
    qpos = q_off[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    mask = jnp.arange(T)[None, None, None, :] <= qpos[:, :, None, None]
    p = jax.nn.softmax(jnp.where(mask, sc, NEG_INF), axis=-1)
    return jnp.einsum("bsht,btc->bshc", p, rows).astype(q.dtype)


def _expanded(p, q, c, k_pe, cfg, cdt):
    """Causal attention over the sequence's own K/V, per head."""
    a = cfg.mla
    B, S, H, _ = q.shape
    k_nope = jnp.einsum("bsr,rhk->bshk", c, p["wk_b"].astype(cdt))
    v = jnp.einsum("bsr,rhk->bshk", c, p["wv_b"].astype(cdt))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None],
                                  (B, S, H, a.qk_rope_head_dim))], -1)
    # chunked_attention takes one head width: V rides zero-padded
    v = jnp.pad(v, ((0, 0),) * 3 + ((0, a.qk_head_dim - a.v_head_dim),))
    out = attn_lib.chunked_attention(q, k, v, causal=True,
                                     q_chunk=cfg.attn_q_chunk,
                                     k_chunk=cfg.attn_k_chunk)
    return out[..., :a.v_head_dim]


def mla_apply(p, x, cfg, *, positions, mode: str = "full", kv_cache=None,
              cur_len=None, chunk_off=None):
    """``attn_apply`` for latent attention; returns (out, new_kv | None).
    mode: full | prefill | chunk | decode (a verify window is not
    served for this attention)."""
    a = cfg.mla
    cdt = cfg.dtype("compute")
    with jax.named_scope("serve.mla"):
        xc = x.astype(cdt)
        q = jnp.einsum("bsd,dhk->bshk", xc, p["wq"].astype(cdt))
        q_nope, q_pe = q[..., :a.qk_nope_head_dim], q[..., a.qk_nope_head_dim:]
        kv = jnp.einsum("bsd,dr->bsr", xc, p["wkv_a"].astype(cdt))
        c = layers.rms_norm(kv[..., :a.kv_lora_rank], p["kv_norm"],
                            a.kv_norm_eps)
        q_pe = layers.rope(q_pe, positions, cfg.rope_theta,
                           cfg.rope_interleave)
        k_pe = layers.rope(kv[..., None, a.kv_lora_rank:], positions,
                           cfg.rope_theta, cfg.rope_interleave)[:, :, 0]
        new_kv = None
        if mode in ("full", "prefill"):
            if mode == "prefill":
                new_kv = kv_cache.write_prompt(_row(c, k_pe, a), None)
            o = _expanded(p, jnp.concatenate([q_nope, q_pe], -1), c, k_pe,
                          cfg, cdt)
        elif mode in ("chunk", "decode"):
            row = _row(c, k_pe, a)
            if mode == "chunk":
                new_kv = kv_cache.write_chunk(row, None, chunk_off)
                off = chunk_off
            else:
                new_kv = kv_cache.append(row, None, cur_len)
                off = jnp.asarray(cur_len, jnp.int32) - 1
            q_abs = jnp.einsum("bshk,rhk->bshr", q_nope,
                               p["wk_b"].astype(cdt))
            qf = jnp.concatenate(
                [q_abs, q_pe, jnp.zeros(q_pe.shape[:-1] + (
                    a.row_lanes - a.row,), cdt)], -1)
            lat = latent_attention(qf, new_kv, q_off=off, cfg=cfg,
                                   decode=mode == "decode")
            o = jnp.einsum("bshr,rhk->bshk", lat[..., :a.kv_lora_rank],
                           p["wv_b"].astype(cdt))
        else:
            raise ValueError(f"latent attention serves no {mode!r} mode")
        out = jnp.einsum("bshk,hkd->bsd", o.astype(cdt), p["wo"].astype(cdt))
    return out.astype(x.dtype), new_kv


def _row(c, k_pe, a):
    """(B, S, 1, row_lanes): one cached row per token, one shared head."""
    pad = jnp.zeros(c.shape[:-1] + (a.row_lanes - a.row,), c.dtype)
    return jnp.concatenate([c, k_pe.astype(c.dtype), pad], -1)[:, :, None]
