"""jit'd public wrapper for the paged-attention decode kernel."""

from __future__ import annotations

import functools

import jax

from .. import on_tpu, per_device
from .kernel import paged_attention as _kernel
from .ref import paged_attention_ref


@jax.jit
def paged_attention(q, k_pool, v_pool, table, cur_len):
    """Dispatch: compiled Pallas on TPU, interpret-mode elsewhere."""
    kern = functools.partial(_kernel, interpret=not on_tpu())
    return per_device(kern, rows=(0, 3, 4))(q, k_pool, v_pool, table,
                                            cur_len)


__all__ = ["paged_attention", "paged_attention_ref"]
