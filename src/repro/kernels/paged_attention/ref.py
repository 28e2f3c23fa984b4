"""Pure-jnp oracle for the paged-attention decode kernel.

Reconstructs the dense layout through the block table exactly the way
``repro.serve.kv_cache.PagedView.gather`` does (unallocated entries
clip to block 0; garbage lanes are masked by ``cur_len``), then runs
the same single-position attention math as
``repro.models.attention.decode_attention`` — so the oracle IS the
gather-based XLA path, inlined.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def gather_kv(k_pool, v_pool, table):
    """Dense ``(B, bpr*block, KV, hd)`` K/V through the block table.

    ``table`` entries < 0 (unallocated) clip to physical block 0; the
    garbage they read is masked by ``cur_len`` downstream, mirroring
    ``PagedView.gather``.
    """
    n_blocks, KV, block, hd = k_pool.shape
    B, bpr = table.shape
    safe = jnp.clip(table, 0)
    kg = k_pool[safe].swapaxes(2, 3).reshape(B, bpr * block, KV, hd)
    vg = v_pool[safe].swapaxes(2, 3).reshape(B, bpr * block, KV, hd)
    return kg, vg


def paged_attention_ref(q, k_pool, v_pool, table, cur_len):
    """q: (B, 1, H, hd); pools: (n_blocks, KV, block, hd);
    table: (B, bpr) int32 (-1 = unallocated); cur_len: (B,) int32.
    Returns (B, 1, H, hd). fp32 math."""
    B, _, H, hd = q.shape
    KV = k_pool.shape[1]
    G = H // KV
    kg, vg = gather_kv(k_pool, v_pool, table)
    T = kg.shape[1]
    qf = (q.astype(jnp.float32) / math.sqrt(hd)).reshape(B, KV, G, hd)
    s = jnp.einsum("bkgd,btkd->bkgt", qf, kg.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    mask = jnp.arange(T)[None, None, None, :] < \
        jnp.asarray(cur_len)[:, None, None, None]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgt,btkd->bkgd", p, vg.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, H, hd).astype(q.dtype)
