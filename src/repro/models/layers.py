"""Shared building blocks: norms, RoPE, MLPs, embeddings."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..dist import sharding as sh


def rms_norm(x, weight=None, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    if weight is not None:
        x = x * weight.astype(jnp.float32)
    return x.astype(dt)


def layer_norm(x, weight=None, bias=None, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    if weight is not None:
        x = x * weight.astype(jnp.float32)
    if bias is not None:
        x = x + bias.astype(jnp.float32)
    return x.astype(dt)


def apply_norm(kind: str, x, params, name: str, eps: float = 0.0):
    """kind: rmsnorm | layernorm | nonparametric_ln (OLMo). ``eps`` 0
    keeps the norm's own default (``cfg.norm_eps`` where it is set)."""
    if kind == "rmsnorm":
        if eps:
            return rms_norm(x, params[name], eps)
        return rms_norm(x, params[name])
    if kind == "layernorm":
        return layer_norm(x, params[name], params.get(name + "_b"))
    if kind == "nonparametric_ln":
        return layer_norm(x, None, None)
    raise ValueError(kind)


def norm_params(b, kind: str, d: int, name: str):
    """Emit norm params into a dict via the Builder (empty if OLMo-style)."""
    out = {}
    if kind == "rmsnorm":
        out[name] = b.p((d,), (sh.EMBED,), init="ones")
    elif kind == "layernorm":
        out[name] = b.p((d,), (sh.EMBED,), init="ones")
        out[name + "_b"] = b.p((d,), (sh.EMBED,), init="zeros")
    return out


def rope(x, positions, theta: float, interleave: bool = False):
    """Rotary embedding. x: (..., seq, heads, head_dim); positions (..., seq).

    Pairs (i, i + d/2) are rotated by frequency i; ``interleave`` pairs
    (2i, 2i + 1) instead (DeepSeek-V3's ``rope_interleave``), with the
    output laid out de-interleaved, evens then odds, as the published
    code leaves it (a dot product of two rotated vectors is the same
    either way)."""
    if interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(
        -jnp.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    dt = x.dtype
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1).astype(dt)


def sinusoidal_positions(n: int, d: int, dtype=jnp.float32):
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(angle), jnp.cos(angle)],
                           axis=-1).astype(dtype)


def sinusoid_at(pos, d: int, dtype=jnp.float32):
    """Sinusoidal embedding at (possibly traced) position(s).

    pos: scalar -> (d,); (B,) vector (per-slot decode positions) -> (B, d).
    """
    dim = jnp.arange(d // 2, dtype=jnp.float32)
    inv = jnp.power(10000.0, 2 * dim / d)
    pos = jnp.asarray(pos)
    angle = (pos.astype(jnp.float32)[..., None] / inv if pos.ndim
             else pos.astype(jnp.float32) / inv)
    return jnp.concatenate([jnp.sin(angle), jnp.cos(angle)],
                           axis=-1).astype(dtype)


def swiglu(x, w_gate, w_up, w_down, compute_dtype):
    """SwiGLU MLP: (silu(x @ w_gate) * (x @ w_up)) @ w_down."""
    x = x.astype(compute_dtype)
    g = jax.nn.silu(jnp.einsum("...d,df->...f", x, w_gate.astype(compute_dtype)))
    u = jnp.einsum("...d,df->...f", x, w_up.astype(compute_dtype))
    return jnp.einsum("...f,fd->...d", g * u, w_down.astype(compute_dtype))


def gelu_mlp(x, w_in, b_in, w_out, b_out, compute_dtype):
    x = x.astype(compute_dtype)
    h = jnp.einsum("...d,df->...f", x, w_in.astype(compute_dtype))
    h = jax.nn.gelu(h + b_in.astype(compute_dtype), approximate=True)
    return (jnp.einsum("...f,fd->...d", h, w_out.astype(compute_dtype))
            + b_out.astype(compute_dtype))
