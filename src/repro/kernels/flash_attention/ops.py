"""jit'd public wrapper for the flash attention kernel."""

from __future__ import annotations

import functools

import jax

from .. import on_tpu, per_device
from .kernel import flash_attention as _kernel
from .ref import attention_ref


@functools.partial(jax.jit, static_argnames=("causal", "blk_q", "blk_k"))
def flash_attention(q, k, v, *, causal: bool = True, blk_q: int = 128,
                    blk_k: int = 128):
    """Token-major ``q (B, S, H, D)``, ``k/v (B, T, KV, D)`` ->
    ``(B, S, H, D)``. The kernel reads head-major blocks, so the
    operands are transposed here: the model's projections emit
    token-major arrays. Compiled Pallas on TPU, interpret elsewhere."""
    kern = functools.partial(_kernel, causal=causal, blk_q=blk_q,
                             blk_k=blk_k, interpret=not on_tpu())
    out = per_device(kern, rows=(0, 1, 2))(
        q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2))
    return out.swapaxes(1, 2)


__all__ = ["flash_attention", "attention_ref"]
