"""Pure-jnp oracle for the flash-prefill (chunked prompt) kernel.

Semantics: a C-token query chunk whose first token sits at absolute
stream position ``q_off[b]`` attends CAUSALLY over the row's cache —
query ``i`` sees exactly lanes ``[0, q_off[b] + i]`` (the chunk's own
K/V included: the caller writes the chunk into the pool before
attending, mirroring ``PagedView.write_chunk`` then read).

The oracle reconstructs the dense layout through the block table the
way ``repro.serve.kv_cache.PagedView.gather`` does (unallocated ``-1``
entries clip to block 0; their garbage is causally masked), then runs
one fp32 masked softmax per query row — so parity against this oracle
is parity against the XLA gather path the kernel replaces.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_prefill_ref(q, k_pool, v_pool, table, q_off):
    """q: (B, C, H, hd); pools: (n_blocks, KV, block, hd);
    table: (B, bpr) int32 (-1 = unallocated); q_off: (B,) int32.
    Returns (B, C, H, hd). fp32 math."""
    B, C, H, hd = q.shape
    KV, block = k_pool.shape[1], k_pool.shape[2]
    G = H // KV
    bpr = table.shape[1]
    safe = jnp.clip(table, 0)
    kg = k_pool[safe].swapaxes(2, 3).reshape(B, bpr * block, KV, hd)
    vg = v_pool[safe].swapaxes(2, 3).reshape(B, bpr * block, KV, hd)
    T = kg.shape[1]
    qf = (q.astype(jnp.float32) / math.sqrt(hd)).reshape(B, C, KV, G, hd)
    s = jnp.einsum("bckgd,btkd->bkgct", qf, kg.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    qpos = jnp.asarray(q_off, jnp.int32)[:, None] \
        + jnp.arange(C, dtype=jnp.int32)[None, :]              # (B, C)
    mask = jnp.arange(T)[None, None, :] <= qpos[:, :, None]    # (B, C, T)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgct,btkd->bkgcd", p, vg.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    return o.transpose(0, 3, 1, 2, 4).reshape(B, C, H, hd).astype(q.dtype)
