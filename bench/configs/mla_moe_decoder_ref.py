"""Plain reference of a DeepSeek-V3 decoder (latent attention and a
mixture of experts), in float32.

Written from the published descriptions, not from the program: the
DeepSeek-V3 report (arXiv:2412.19437, §2.1) and the ``deepseek_v3``
model of the configuration's ``config.json`` (Moonlight-16B-A3B):

- pre-norm blocks, RMSNorm with a gain (eps ``rms_norm_eps``), untied
  output head, no biases;
- multi-head latent attention without a q-LoRA: ``q = h W_q`` split
  into ``qk_nope_head_dim`` and ``qk_rope_head_dim`` per head;
  ``[c, k_pe] = h W_kv_a``, ``c`` RMS-normed with the library's default
  eps 1e-6 (the published code builds that norm without
  ``rms_norm_eps``); head ``h``'s key ``[c W_UK_h, k_pe]`` and value
  ``c W_UV_h`` (``W_kv_b`` split into its K and V halves); RoPE with
  base ``rope_theta`` on ``q_pe`` and the one shared ``k_pe``, pairing
  lanes (2i, 2i+1) as ``rope_interleave`` does; softmax scale
  ``1/sqrt(nope + rope)``; computed in the expanded form, per head;
- the first ``first_k_dense_replace`` layers end in a SwiGLU of
  ``intermediate_size``; the rest in a mixture of experts routed by
  noaux_tc with one group: scores ``s = sigmoid(h W_r)``, the
  ``num_experts_per_tok`` experts with the largest ``s +
  e_score_correction_bias`` chosen, weighted by their ``s`` normalised
  over the chosen (``norm_topk_prob``) and scaled by
  ``routed_scaling_factor``; plus ``n_shared_experts`` shared experts,
  one SwiGLU of ``n_shared_experts * moe_intermediate_size``, always
  on.

Departures, each the configuration's stated share of one chip of a
deployment (its JSON file): ``num_hidden_layers`` layers (a pipeline
stage); the router spans ``router_experts`` but only the
``n_routed_experts`` held from ``expert_offset`` are computed, and the
entries routed to the others add nothing, as on that chip; the
vocabulary is the slice ``vocab_size``. No cache and no batching: the
whole sequence at once, attention in query blocks so that a long one
fits, every matrix product at ``Precision.HIGHEST``.

``quant="fp8"`` is the control, as in ``dense_decoder_ref.py``: the
inputs of every weight product (the router's included) and of the two
attention products rounded to float8 e4m3 with an absmax scale. Here
the whole forward runs so, and the token it puts first is scored
against the float32 forward (``dense_decoder_ref.py`` scores it against
the float32 head of the fp8 forward's hidden states).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
KV_NORM_EPS = 1e-6


def _fp8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(eq, x, w, quant, w_in_axes):
    """``einsum(eq, x, w)`` with x's last axis and w's ``w_in_axes``
    contracted; fp8 rounds x per row and w per output column."""
    if quant == "fp8":
        x = _fp8(x, axis=-1)
        w = _fp8(w, axis=w_in_axes)
    return jnp.einsum(eq, x, w, precision=HI)


def _rms(x, gain, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * gain


def _rope(x, pos, theta):
    """x: (S, heads, d). Lanes (2i, 2i+1) rotate by pos * theta^(-2i/d);
    the result is laid out evens then odds, as the published code
    leaves it."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]     # (S, d/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(q, k, v, block, quant=None):
    """Causal attention per head. q, k: (S, H, dk); v: (S, H, dv);
    S % block == 0."""
    if quant == "fp8":
        q, k = _fp8(q, axis=-1), _fp8(k, axis=-1)
        v = _fp8(v, axis=0)
    S, H, dk = q.shape
    qb = q.reshape(S // block, block, H, dk)
    kpos = jnp.arange(S)

    def one(args):
        qq, b = args
        s = jnp.einsum("qhd,shd->hqs", qq, k, precision=HI) / jnp.sqrt(
            jnp.float32(dk))
        qpos = b * block + jnp.arange(block)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if quant == "fp8":
            p = _fp8(p, axis=-1)
        return jnp.einsum("hqs,shd->qhd", p, v, precision=HI)

    out = jax.lax.map(one, (qb, jnp.arange(S // block)))
    return out.reshape(S, H, v.shape[-1])


def attention(a, c, h, pos, quant=None, block=256):
    """The latent attention of one layer: hidden (S, D) -> (S, D)."""
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    r = c["kv_lora_rank"]
    S = h.shape[0]
    q = _mm("sd,dhk->shk", h, a["wq"], quant, 0)
    kv = _mm("sd,dr->sr", h, a["wkv_a"], quant, 0)
    lat = _rms(kv[:, :r], a["kv_norm"], KV_NORM_EPS)
    k_pe = _rope(kv[:, None, r:], pos, c["rope_theta"])        # (S, 1, rope)
    q_pe = _rope(q[..., nope:], pos, c["rope_theta"])
    k_nope = _mm("sr,rhk->shk", lat, a["wk_b"], quant, 0)
    v = _mm("sr,rhk->shk", lat, a["wv_b"], quant, 0)
    H = q.shape[1]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (S, H, rope))], -1)
    o = _attention(jnp.concatenate([q[..., :nope], q_pe], -1), k, v, block,
                   quant)
    return _mm("sd,dm->sm", o.reshape(S, -1),
               a["wo"].reshape(-1, a["wo"].shape[-1]), quant, 0)


def _swiglu(h, wg, wu, wd, quant):
    g = _mm("sd,df->sf", h, wg, quant, 0)
    u = _mm("sd,df->sf", h, wu, quant, 0)
    return _mm("sf,fd->sd", jax.nn.silu(g) * u, wd, quant, 0)


def moe(m, c, h, quant=None):
    """The experts held here, and the shared experts, for hidden (S, D):
    the routing spans every expert of the router, and the entries on
    experts not held add nothing."""
    logits = _mm("sd,de->se", h, m["router"], quant, 0)
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + m["e_score_correction_bias"],
                           c["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if c["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * c["routed_scaling_factor"]
    held = c["expert_offset"] + jnp.arange(c["n_routed_experts"])
    gate = jnp.sum(jnp.where(idx[:, :, None] == held[None, None, :],
                             w[:, :, None], 0.0), axis=1)       # (S, E)
    g = _mm("sd,edf->sef", h, m["w_gate"], quant, 1)
    u = _mm("sd,edf->sef", h, m["w_up"], quant, 1)
    y = _mm("sef,efd->sed", jax.nn.silu(g) * u, m["w_down"], quant, 1)
    out = jnp.einsum("sed,se->sd", y, gate, precision=HI)
    return out + _swiglu(h, m["shared_gate"], m["shared_up"],
                         m["shared_down"], quant)


def _f32(tree):
    return jax.tree.map(lambda w: w.astype(jnp.float32), tree)


def hidden(params, c, tokens, quant=None, block=256):
    """Final normed hidden states (S, D) of ``tokens`` (S,), S a
    multiple of ``block``. Weights are cast to float32 one layer at a
    time."""
    S = tokens.shape[0]
    pos = jnp.arange(S)
    eps = c["rms_norm_eps"]
    x = params["embed"].astype(jnp.float32)[tokens]
    k = c["first_k_dense_replace"]

    def block_attn(x, lp):
        return x + attention(lp["attn"], c, _rms(x, lp["ln_attn"], eps), pos,
                             quant, block)

    for i in range(k):
        lp = _f32(jax.tree.map(lambda w: w[i], params["layers"]))
        mp = _f32(jax.tree.map(lambda w: w[i], params["dense_mlp"]))
        x = block_attn(x, lp)
        x = x + _swiglu(_rms(x, lp["ln_mlp"], eps), mp["w_gate"],
                        mp["w_up"], mp["w_down"], quant)

    def layer(x, xs):
        lp, mp = _f32(xs)
        x = block_attn(x, lp)
        return x + moe(mp, c, _rms(x, lp["ln_mlp"], eps), quant), None

    rest = jax.tree.map(lambda w: w[k:], params["layers"])
    x, _ = jax.lax.scan(layer, x, (rest, params["moe"]))
    return _rms(x, params["ln_final"].astype(jnp.float32), eps)


def head(params, c, h, quant=None):
    """Logits (n, V_padded) of hidden rows ``h`` (n, D)."""
    return _mm("sd,dv->sv", h, params["unembed"].astype(jnp.float32), quant,
               0)


@functools.partial(jax.jit, static_argnames=("c_items", "quant", "n_out"))
def _gaps(params, tokens, first, served, c_items, quant, n_out):
    c = dict(c_items)

    def rows(q):
        h = hidden(params, c, tokens, quant=q)
        return jax.lax.dynamic_slice_in_dim(h, first, n_out, axis=0)
    ref = head(params, c, rows(None))
    best = ref.max(-1)
    if quant is None:
        pick = served
    else:             # the whole forward in the lower precision
        pick = jnp.argmax(head(params, c, rows(quant), quant=quant), axis=-1)
    return best - jnp.take_along_axis(ref, pick[:, None], axis=1)[:, 0]


def served_gaps(params, c, prompt, served, pad_to, quant=None):
    """Per served token, how far its reference logit lies below the
    reference's best at that position (0 where it is the best).

    ``prompt`` (L,) and ``served`` (n,) token ids; the reference reads
    prompt + served[:-1] and scores position L-1+i against served[i].
    With ``quant`` set it instead scores the token that the whole
    forward in the lower precision puts first at each of those
    positions against the float32 reference (the control)."""
    L, n = len(prompt), len(served)
    # whole 256-row buckets where they fit: few shapes, so few
    # compilations of _gaps
    n_out = min(-(-n // 256) * 256, pad_to - L + 1)
    if n_out < n:
        raise ValueError(f"{L} prompt + {n} served tokens exceed the "
                         f"reference length {pad_to}")
    tokens = np.zeros(pad_to, np.int32)
    tokens[:L] = prompt
    tokens[L:L + n - 1] = served[:-1]
    srv = np.zeros(n_out, np.int32)
    srv[:n] = served
    c_items = tuple(sorted((k, v) for k, v in c.items()
                           if isinstance(v, (int, float, str, bool))))
    with jax.default_matmul_precision("highest"):
        g = _gaps(params, jnp.asarray(tokens), L - 1, jnp.asarray(srv),
                  c_items, quant, n_out)
    return np.asarray(g)[:n]
