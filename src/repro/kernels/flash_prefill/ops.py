"""jit'd public wrapper for the flash-prefill chunk kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import on_tpu, per_device
from .kernel import flash_prefill as _kernel
from .ref import flash_prefill_ref


@jax.jit
def flash_prefill(q, k_pool, v_pool, table, q_off):
    """Dispatch: compiled Pallas on TPU, interpret-mode elsewhere."""
    kern = functools.partial(_kernel, interpret=not on_tpu())
    return per_device(kern, rows=(0, 3, 4))(q, k_pool, v_pool, table,
                                            q_off)


@jax.jit
def flash_verify(q, k_pool, v_pool, table, q_off):
    """Speculative-decode verify-window entry point.

    Same kernel, different caller contract: ``q (B, W, hq, hd)`` is a
    k+1-token speculative window whose first query sits at per-row
    ``q_off = cur_len - 1`` (the chunk contract — query ``j`` sees
    lanes ``[0, q_off + j]`` — is exactly the verify visibility rule).
    The window is tiny (W = k+1, typically ≤ 8), so the query tile
    ``W·G`` can sit under the fp32 (8, 128) sublane minimum on real
    TPUs: pad the window up front, slice the pad off after. Pad
    queries read positions past the window through the same clamped
    block map (an out-of-range table entry clamps to the drop/0 block);
    their outputs are garbage and discarded, and query rows are
    independent, so real rows are untouched.
    """
    B, W, hq, hd = q.shape
    g = hq // k_pool.shape[1]
    wp = W
    while (wp * g) % 8:
        wp += 1
    if wp != W:
        q = jnp.pad(q, ((0, 0), (0, wp - W), (0, 0), (0, 0)))
    kern = functools.partial(_kernel, interpret=not on_tpu())
    out = per_device(kern, rows=(0, 3, 4))(q, k_pool, v_pool, table, q_off)
    return out[:, :W]


__all__ = ["flash_prefill", "flash_verify", "flash_prefill_ref"]
