"""Plain reference of a dense decoder-only transformer, in float32.

Written from the published descriptions, not from the program:

- OLMo (arXiv:2402.00838): pre-norm blocks with non-parametric layer
  norm (no gain, no bias, eps 1e-5), SwiGLU MLP, rotary position
  embeddings (rotate-half form), no biases, tied input and output
  embeddings.
- Qwen2 (arXiv:2407.10671): RMSNorm with a gain (eps 1e-6), biases on
  the q, k and v projections, grouped-query attention, RoPE with base
  1e6, SwiGLU MLP, untied output head.

Which of these a configuration uses is read from its JSON file. The
weights are a tree in the layout ``bench/weights.py`` draws; nothing
of the program is imported. Every matrix product runs at
``Precision.HIGHEST``, so on a TPU it is float32 and not bf16 passes.
Attention runs in query blocks so that a long sequence fits.

``quant="fp8"`` is the control: the same forward with the inputs of
every weight product (activations per row, weights per output column)
rounded to float8 e4m3 with an absmax scale, as an fp8 serving path
would compute them, and so are the inputs of the two attention
products. Scores, the softmax and the residual stream stay float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


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


def _norm(x, gain, c):
    if c["layer_norm"] == "nonparametric":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + c["layer_norm_eps"])
    y = x / jnp.sqrt((x * x).mean(-1, keepdims=True) + c["layer_norm_eps"])
    return y * gain


def _rope(x, pos, theta):
    """x: (S, heads, hd). Rotate-half: pair i with i + hd/2."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]     # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(q, k, v, block, quant=None):
    """Causal GQA. q: (S, H, hd); k, v: (S, KV, hd); S % block == 0.
    Query head h reads KV head h // (H / KV). With ``quant="fp8"`` the
    inputs of both products (q and k per head row, the probabilities
    per row, v per head column) are rounded to e4m3 as well."""
    if quant == "fp8":
        q, k = _fp8(q, axis=-1), _fp8(k, axis=-1)
        v = _fp8(v, axis=0)
    S, H, hd = q.shape
    KV = k.shape[1]
    G = H // KV
    qg = q.reshape(S // block, block, KV, G, hd)
    kpos = jnp.arange(S)

    def one(args):
        qb, b = args
        s = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HI) / jnp.sqrt(
            jnp.float32(hd))
        qpos = b * block + jnp.arange(block)
        s = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if quant == "fp8":
            p = _fp8(p, axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI)

    out = jax.lax.map(one, (qg, jnp.arange(S // block)))
    return out.reshape(S, H, hd)


def hidden(params, c, tokens, quant=None, block=256):
    """Final normed hidden states (S, D) of ``tokens`` (S,), S a
    multiple of ``block``."""
    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = params["embed"].astype(jnp.float32)[tokens]
    bias = c["attention_bias"]

    def layer(x, lp):
        a = lp["attn"]
        h = _norm(x, lp.get("ln_attn"), c)
        q = _mm("sd,dhk->shk", h, a["wq"], quant, 0)
        k = _mm("sd,dhk->shk", h, a["wk"], quant, 0)
        v = _mm("sd,dhk->shk", h, a["wv"], quant, 0)
        if bias:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q = _rope(q, pos, c["rope_theta"])
        k = _rope(k, pos, c["rope_theta"])
        o = _attention(q, k, v, block, quant)
        x = x + _mm("sd,dm->sm", o.reshape(S, -1),
                    a["wo"].reshape(-1, a["wo"].shape[-1]), quant, 0)
        h = _norm(x, lp.get("ln_mlp"), c)
        m = lp["mlp"]
        g = _mm("sd,df->sf", h, m["w_gate"], quant, 0)
        u = _mm("sd,df->sf", h, m["w_up"], quant, 0)
        x = x + _mm("sf,fd->sd", jax.nn.silu(g) * u, m["w_down"], quant, 0)
        return x, None

    layers = jax.tree.map(lambda w: w.astype(jnp.float32), params["layers"])
    x, _ = jax.lax.scan(layer, x, layers)
    return _norm(x, params.get("ln_final"), c)


def head(params, c, h, quant=None):
    """Logits (n, V_padded) of hidden rows ``h`` (n, D)."""
    if c["tie_word_embeddings"]:
        w = params["embed"].astype(jnp.float32).T
    else:
        w = params["unembed"].astype(jnp.float32)
    return _mm("sd,dv->sv", h, w, quant, 0)


@functools.partial(jax.jit, static_argnames=("c_items", "quant", "n_out"))
def _gaps(params, tokens, first, served, c_items, quant, n_out):
    c = dict(c_items)
    h = hidden(params, c, tokens, quant=quant)
    rows = jax.lax.dynamic_slice_in_dim(h, first, n_out, axis=0)
    ref = head(params, c, rows)
    best = ref.max(-1)
    got = jnp.take_along_axis(ref, served[:, None], axis=1)[:, 0]
    if quant is None:
        return best - got
    low = head(params, c, rows, quant=quant)
    pick = jnp.argmax(low, axis=-1)
    return best - jnp.take_along_axis(ref, pick[:, None], axis=1)[:, 0]


def served_gaps(params, c, prompt, served, pad_to, quant=None):
    """Per served token, how far its reference logit lies below the
    reference's best at that position (0 where it is the best).

    ``prompt`` (L,) and ``served`` (n,) token ids; the reference reads
    prompt + served[:-1] and scores position L-1+i against served[i].
    With ``quant`` set it instead scores the token the lower precision
    puts first at each of those positions (the control)."""
    L, n = len(prompt), len(served)
    n_out = -(-n // 64) * 64
    if L - 1 + n_out > pad_to:
        raise ValueError(f"{L} prompt + {n} served tokens exceed the "
                         f"reference length {pad_to}")
    tokens = np.zeros(pad_to, np.int32)
    tokens[:L] = prompt
    tokens[L:L + n - 1] = served[:-1]
    srv = np.zeros(n_out, np.int32)
    srv[:n] = served
    c_items = tuple(sorted((k, v) for k, v in c.items()
                           if isinstance(v, (int, float, str, bool))))
    g = _gaps(params, jnp.asarray(tokens), L - 1, jnp.asarray(srv),
              c_items, quant, n_out)
    return np.asarray(g)[:n]
