"""Mixture-of-Experts layer — the paper's own motivating architecture
(Fig. 1: RNN layers dynamically connected through an MoE) and the
"conditional computation" frontier named in §8 / ref [38].

TPU-native dispatch (GShard-style grouping + sort-based capacity):

- a **group** dimension (one group per sequence) keeps routing local:
  argsort / position-in-expert / scatter / gather are all vmapped over
  groups, and groups shard over the ``batch`` axes — so GSPMD never
  replicates token tensors across the mesh (a global sort-based dispatch
  measured 500 GiB/device on dbrx train_4k before this change);
- within a group, tokens are argsorted by expert id, positioned within
  the per-group capacity C_g via a first-occurrence offset, scattered
  into a (G, E, C_g+1, D) buffer (slot C_g = overflow/drop row);
- the per-expert SwiGLU is a dense einsum with E as a *batch* dim,
  sharded over ``model`` for expert parallelism (dbrx 16e/16-way) — the
  einsum is then fully local; qwen2-moe's 60 experts fall back to
  tensor parallelism over the expert FFN dim;
- everything is reverse-differentiable through the gather/scatter
  transpose pair.

Serving uses the **dropless expert-share** layer (``dropless_moe``,
``cfg.moe.dispatch == "dropless"``): the router spans all
``router_experts`` at published width, the layer holds ``n_experts``
of them from ``expert_offset`` (one chip's share under expert
parallelism), and the entries routed to them are computed as one
grouped matmul over entries sorted by expert — no capacity, so no
token is dropped and pad rows cannot displace real ones. Entries on
experts held elsewhere add nothing here; the layer stands in for no
absent chip and no exchange.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..dist import sharding as sh
from . import layers


def moe_params(b, cfg, d_model: int):
    m = cfg.moe
    p = {
        "router": b.p((d_model, m.router_experts), (sh.EMBED, None),
                      scale=0.1),
        "w_gate": b.p((m.n_experts, d_model, m.d_ff_expert),
                      (sh.EXPERT, sh.EMBED, sh.EXPERT_MLP), fan_in=d_model),
        "w_up": b.p((m.n_experts, d_model, m.d_ff_expert),
                    (sh.EXPERT, sh.EMBED, sh.EXPERT_MLP), fan_in=d_model),
        "w_down": b.p((m.n_experts, m.d_ff_expert, d_model),
                      (sh.EXPERT, sh.EXPERT_MLP, sh.EMBED),
                      fan_in=m.d_ff_expert),
    }
    if m.router == "sigmoid":
        # float32, as published: it only reorders the choice
        p["e_score_correction_bias"] = b.p(
            (m.router_experts,), (None,), init="zeros", dtype=jnp.float32)
    if m.n_shared_experts:
        p["shared_gate"] = b.p((d_model, m.d_ff_shared), (sh.EMBED, sh.MLP))
        p["shared_up"] = b.p((d_model, m.d_ff_shared), (sh.EMBED, sh.MLP))
        p["shared_down"] = b.p((m.d_ff_shared, d_model), (sh.MLP, sh.EMBED))
    return p


@jax.custom_vjp
def _dispatch_gather(xg_pad, slot_token, token_slot, dropped):
    """buf[g, slot] = xg_pad[g, slot_token[g, slot]].

    Backward is a GATHER (not the scatter-add transpose XLA would emit —
    which lowers on CPU with f32 shadow copies of the whole stream):
    every kept slot holds exactly one token, so
    g_x[t] = sum_k (1-dropped[t,k]) * g_buf[token_slot[t,k]] exactly.
    """
    return jnp.take_along_axis(xg_pad, slot_token[..., None], axis=1)


def _dispatch_fwd(xg_pad, slot_token, token_slot, dropped):
    out = _dispatch_gather(xg_pad, slot_token, token_slot, dropped)
    return out, (token_slot, dropped, xg_pad.shape)


def _dispatch_bwd(res, g):
    token_slot, dropped, xshape = res
    G, S, K = token_slot.shape
    picked = jnp.take_along_axis(
        g, token_slot.reshape(G, S * K)[..., None], axis=1)
    picked = picked.reshape(G, S, K, -1)
    picked = jnp.where(dropped[..., None], 0.0, picked)
    g_x = picked.sum(axis=2)                             # (G, S, D)
    g_x = jnp.concatenate(
        [g_x, jnp.zeros((G, 1, g_x.shape[-1]), g_x.dtype)], axis=1)
    return g_x, None, None, None


_dispatch_gather.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine_gather(h_flat, token_slot, slot_token, dropped):
    """contrib[g, s, k] = h_flat[g, token_slot[g, s, k]].

    Backward: g_h[slot] = g_contrib[slot_token[slot]] (empty slots get
    zero via the S sentinel); exact because slot->token is injective on
    kept slots and dropped entries carry zero gate.
    """
    G, S, K = token_slot.shape
    out = jnp.take_along_axis(
        h_flat, token_slot.reshape(G, S * K)[..., None], axis=1)
    return out.reshape(G, S, K, h_flat.shape[-1])


def _combine_fwd(h_flat, token_slot, slot_token, dropped):
    out = _combine_gather(h_flat, token_slot, slot_token, dropped)
    return out, (slot_token, token_slot.shape, h_flat.shape)


def _combine_bwd(res, g):
    slot_token, (G, S, K), hshape = res
    g_flat = g.reshape(G, S * K, -1)
    g_pad = jnp.concatenate(
        [g_flat, jnp.zeros((G, 1, g_flat.shape[-1]), g_flat.dtype)], axis=1)
    # slot -> flattened (s*K + k) source index; sentinel S -> zero row
    # slot_token stores the token index; we need (token, k). Since a kept
    # slot corresponds to exactly one routed entry, we store s*K+k there
    # (see route()), so this lookup is direct.
    g_h = jnp.take_along_axis(g_pad, slot_token[..., None], axis=1)
    return g_h, None, None, None


_combine_gather.defvjp(_combine_fwd, _combine_bwd)


def _group_capacity(group_tokens: int, cfg) -> int:
    m = cfg.moe
    c = int(group_tokens * m.top_k * m.capacity_factor / m.n_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8


def moe_mlp(p: Dict, x: jax.Array, cfg, rules=None
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B, S, D) -> (out, aux_losses). Groups = sequences (dim B).

    Decode (S == 1) regroups to ONE batch-wide group: per-sequence
    groups would give every single token its own E x C_min expert
    buffer (measured 25x FLOPs waste on dbrx decode_32k).
    """
    m = cfg.moe
    B0, S0, D0 = x.shape
    regrouped = S0 == 1 and B0 > 1
    if regrouped:
        x = x.reshape(1, B0, D0)
    G, S, D = x.shape          # group dim = batch dim
    E, K = m.n_experts, m.top_k
    C = _group_capacity(S, cfg)
    cdt = cfg.dtype("compute")

    xg = x.astype(cdt)                                   # (G, S, D)
    logits = jnp.einsum("gsd,de->gse", xg.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert_idx = jax.lax.top_k(probs, K)           # (G, S, K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # ---- per-group sort-based routing: integer slot maps only ----------
    # All big-tensor data movement below is GATHERS (their transposes,
    # scatter-adds, appear only in backward on the (E, C, D) side) — a
    # scatter-based dispatch lowers with f32 shadow copies of the
    # (S*K, D) stream (measured +24 GiB/device on dbrx train_4k).
    def route(eidx):
        """eidx: (S, K) -> slot maps (all integer, all tiny)."""
        flat_e = eidx.reshape(-1)                        # (S*K,)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        group_start = jnp.searchsorted(sorted_e, sorted_e, side="left")
        pos_in_e = jnp.arange(S * K) - group_start
        keep = pos_in_e < C
        dest_c = jnp.where(keep, pos_in_e, C)            # C = drop slot
        slot = sorted_e * (C + 1) + dest_c
        # slot -> source token (dispatch); S = "no token" sentinel
        slot_token = jnp.full((E * (C + 1),), S, jnp.int32)
        slot_token = slot_token.at[slot].set(
            (order // K).astype(jnp.int32), mode="drop")
        # slot -> routed-entry index s*K+k (combine bwd); S*K = sentinel
        slot_entry = jnp.full((E * (C + 1),), S * K, jnp.int32)
        slot_entry = slot_entry.at[slot].set(order.astype(jnp.int32),
                                             mode="drop")
        # drop rows are cleared: they must hold NO token (zeros flow)
        drop_rows = jnp.arange(E) * (C + 1) + C
        slot_token = slot_token.at[drop_rows].set(S)
        slot_entry = slot_entry.at[drop_rows].set(S * K)
        # token -> its k slots (original (S, K) order)
        pos_orig = jnp.zeros((S * K,), jnp.int32).at[order].set(
            dest_c.astype(jnp.int32))
        token_slot = (flat_e * (C + 1) + pos_orig).reshape(S, K)
        return slot_token, slot_entry, token_slot, keep

    slot_token, slot_entry, token_slot, keep = jax.vmap(route)(expert_idx)

    # dropped = routed entries whose slot is a drop row
    dropped = (token_slot % (C + 1)) == C

    # dispatch: one gather (G, E*(C+1), D); sentinel rows gather zeros
    xg_pad = jnp.concatenate([xg, jnp.zeros((G, 1, D), cdt)], axis=1)
    buf = _dispatch_gather(xg_pad, slot_token, token_slot, dropped)
    buf = buf.reshape(G, E, C + 1, D)
    buf = sh.constrain(buf, rules, (sh.BATCH, sh.EXPERT, None, None))
    be = buf[:, :, :C]                                   # (G, E, C, D)

    # ---- dense per-expert SwiGLU; E is a sharded batch dim of the einsum
    g = jax.nn.silu(jnp.einsum("gecd,edf->gecf", be, p["w_gate"].astype(cdt)))
    u = jnp.einsum("gecd,edf->gecf", be, p["w_up"].astype(cdt))
    h = jnp.einsum("gecf,efd->gecd", g * u, p["w_down"].astype(cdt))
    h = sh.constrain(h, rules, (sh.BATCH, sh.EXPERT, None, None))

    # ---- combine: one gather (G, S, K, D) + weighted sum over K ---------
    h_flat = jnp.concatenate(
        [h, jnp.zeros((G, E, 1, D), h.dtype)], axis=2).reshape(
            G, E * (C + 1), D)
    contrib = _combine_gather(h_flat, token_slot, slot_entry, dropped)
    gate_eff = jnp.where(dropped, 0.0, gate).astype(h.dtype)
    out = jnp.einsum("gskd,gsk->gsd", contrib, gate_eff)
    out = sh.constrain(out, rules, (sh.BATCH, None, None))

    if m.n_shared_experts:
        out = out + layers.swiglu(xg, p["shared_gate"], p["shared_up"],
                                  p["shared_down"], cdt)

    # ---- aux losses (Switch-style load balance + router z-loss) ----------
    me = probs.mean(axis=(0, 1))                         # (E,)
    one_hot_top1 = jax.nn.one_hot(expert_idx[..., 0], E, dtype=jnp.float32)
    ce = one_hot_top1.mean(axis=(0, 1))
    aux = {
        "moe_load_balance": E * jnp.sum(me * ce),
        "moe_z_loss": jnp.mean(
            jax.scipy.special.logsumexp(logits, -1) ** 2),
    }
    out = out.astype(x.dtype)
    if regrouped:
        out = out.reshape(B0, S0, D0)
    return out, aux


# =========================== dropless expert share ==========================

def route(p: Dict, x: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    """Routing over all ``router_experts``, in float32: x (T, D) ->
    (weights (T, K) float32, experts (T, K) int32).

    ``router="sigmoid"`` is DeepSeek-V3's noaux_tc with one group:
    scores ``s = sigmoid(x W_r)``, the top k chosen by
    ``s + e_score_correction_bias``, weighted by ``s``; ``"softmax"``
    chooses and weighs by the softmax. The k weights are renormalised
    (``norm_topk``) and scaled by ``routed_scale``."""
    m = cfg.moe
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    if m.router == "sigmoid":
        s = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(
            s + p["e_score_correction_bias"].astype(jnp.float32), m.top_k)
        w = jnp.take_along_axis(s, idx, axis=-1)
    else:
        w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), m.top_k)
    if m.norm_topk:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * m.routed_scale, idx


def dropless_moe(p: Dict, x: jax.Array, cfg, valid=None
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B, S, D) -> (out, counts). The part of the layer's output the
    experts held here give, plus the shared experts (computed once, as
    every chip computes them alike).

    ``valid`` (B, S) bool marks real tokens; the entries of the others
    (masked slots, pad lanes past a prompt's end, whose outputs the
    caller discards) are routed but not computed. ``counts``:
    ``moe_routed`` (entries of real tokens) and ``moe_local`` (those
    on the experts held here), int32."""
    from ..kernels.grouped_matmul.ops import TM, expert_gmm
    m = cfg.moe
    B, S, D = x.shape
    T, K, E = B * S, m.top_k, m.n_experts
    cdt = cfg.dtype("compute")
    with jax.named_scope("serve.moe"):
        xf = x.reshape(T, D).astype(cdt)
        w, idx = route(p, xf, cfg)
        real = (jnp.ones((T,), bool) if valid is None
                else jnp.asarray(valid, bool).reshape(T))
        e = idx - m.expert_offset
        local = (e >= 0) & (e < E) & real[:, None]
        # entries sorted by held expert; the rest (E) after them
        key = jnp.where(local, e, E).reshape(-1)               # (T*K,)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.sum(key[:, None] == jnp.arange(E)[None, :],
                        axis=0).astype(jnp.int32)
        Mp = -(-T * K // TM) * TM
        xs = xf[jnp.pad(order // K, (0, Mp - T * K))]         # (Mp, D)
        g = expert_gmm(xs, p["w_gate"].astype(cdt), sizes)
        u = expert_gmm(xs, p["w_up"].astype(cdt), sizes)
        # rows past the local entries come back as zeros, and so do
        # their weights
        y = expert_gmm(jax.nn.silu(g) * u, p["w_down"].astype(cdt),
                       sizes)[:T * K]
        ws = jnp.where(local.reshape(-1), w.reshape(-1), 0.0)[order]
        y = y.astype(jnp.float32) * ws[:, None]
        inv = jnp.zeros((T * K,), jnp.int32).at[order].set(
            jnp.arange(T * K, dtype=jnp.int32))
        out = y[inv].reshape(T, K, D).sum(axis=1)
        if m.n_shared_experts:
            out = out + layers.swiglu(xf, p["shared_gate"], p["shared_up"],
                                      p["shared_down"], cdt)
    counts = {"moe_routed": (jnp.sum(real) * K).astype(jnp.int32),
              "moe_local": jnp.sum(sizes)}
    return out.astype(x.dtype).reshape(B, S, D), counts
