"""Pallas TPU flash-prefill kernel: causal chunk attention THROUGH the
block table (GQA-aware, gather-free).

The chunked-prefill counterpart of ``kernels.paged_attention``: a
C-token query chunk at stream offset ``q_off[b]`` attends over the
row's prior K/V — which lives paged in the shared pool — without ever
reconstructing the dense ``(rows, max_len, KV, hd)`` layout. The block
table and per-row offsets ride in as **scalar-prefetch** operands
(``pltpu.PrefetchScalarGridSpec``), resident in SMEM before the body
runs, so the BlockSpec index maps chase the indirection: grid step
``(b, h, j)`` DMAs exactly physical block ``table[b, j]`` HBM→VMEM.

Layout/behaviour contract (shared with ``ref.py`` and
``serve.kv_cache.PagedView``):

- pools are head-major ``(n_blocks, KV, block, hd)`` — one layer's
  slice of the cache's ``(L, n_blocks, ...)`` pool, read as
  ``(1, 1, block, hd)`` blocks (the TPU compiler's tiling rule, see
  ``kernels.paged_attention``); the chunk's OWN K/V must be in the
  pool before the call (``PagedView.write_chunk`` first);
- causal: query ``i`` of row ``b`` sees lanes ``[0, q_off[b] + i]``
  and nothing else — ragged-tail/garbage lanes beyond the last real
  query are only ever visible to garbage queries the caller discards;
- ``table`` entries < 0 (unallocated) clip to physical block 0, same
  lanes the gather path clips, masked identically;
- blocks at or beyond ``ceil((q_off + C) / block)`` are clamped to the
  last visible block in the index map, so the sequential-grid pipeline
  elides their DMAs, and ``pl.when`` skips their FLOPs;
- the online-softmax accumulator lives in VMEM scratch across the
  innermost (sequential) block axis;
- a latent pool (``v_pool=None``) is its own value, as in
  ``kernels.paged_attention``: each block is fetched once.

Grid: ``(B, KV, nb)``; nb = blocks_per_row, innermost/sequential.
VMEM per step: q (C·G·hd) + k/v (block·hd) + acc (C·G·hd fp32) +
m/l (C·G) — a chunk is a few KB at serving chunk sizes. HBM traffic
per row is ``q_off[b] + C`` tokens of K/V, not ``max_len``, and the
dense layout never exists.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fp_kernel(table_ref, off_ref, q_ref, *refs, block: int, nb: int,
               C: int, G: int, scale: float, latent: bool):
    """Grid: (B, KV, nb); nb innermost/sequential."""
    if latent:
        k_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    b, j = pl.program_id(0), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    off = off_ref[b]
    R = C * G                                  # query rows, c-major

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (R, hd)
        k = k_ref[0, 0].astype(jnp.float32)                # (block, hd)
        v = k if latent else v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qpos = off + jax.lax.broadcasted_iota(jnp.int32, (R, block), 0) // G
        pos = j * block + jax.lax.broadcasted_iota(jnp.int32, (R, block), 1)
        s = jnp.where(pos <= qpos, s, NEG_INF)             # causal + ragged
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + p.sum(axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # whole block beyond the chunk's last visible lane -> skip FLOPs
    # (its DMA was already elided by the clamped index map)
    pl.when(j * block <= off + C - 1)(_compute)

    @pl.when(j == nb - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def flash_prefill(q, k_pool, v_pool, table, q_off, *,
                  interpret: bool = True, scale=None):
    """q: (B, C, H, hd); k/v_pool: (n_blocks, KV, block, hd), v_pool
    None for a latent pool; table: (B, bpr) int32; q_off: (B,) int32
    -> (B, C, H, hd). ``scale`` defaults to ``1/sqrt(hd)``."""
    B, C, H, hd = q.shape
    KV, block = k_pool.shape[1], k_pool.shape[2]
    G = H // KV
    bpr = table.shape[1]
    latent = v_pool is None
    scale = 1.0 / math.sqrt(hd) if scale is None else float(scale)
    # (B, C, KV, G, hd) -> (B, KV, C*G, hd): one (chunk x group) tile
    # per KV head, query rows c-major so row r is (c = r // G, g = r % G)
    qg = q.reshape(B, C, KV, G, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B, KV, C * G, hd)
    table = jnp.asarray(table, jnp.int32)
    q_off = jnp.asarray(q_off, jnp.int32)

    def kv_map(b, h, j, table_ref, off_ref):
        # Clamp past-the-end blocks to the last visible one: the
        # pipeline sees an unchanged block index and skips the DMA.
        last = jnp.maximum((off_ref[b] + C - 1) // block, 0)
        jj = jnp.minimum(j, last)
        return (jnp.maximum(table_ref[b, jj], 0), h, 0, 0)

    pools = (k_pool,) if latent else (k_pool, v_pool)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, bpr),
        in_specs=[
            pl.BlockSpec((1, 1, C * G, hd),
                         lambda b, h, j, t, c: (b, h, 0, 0)),
        ] + [pl.BlockSpec((1, 1, block, hd), kv_map)] * len(pools),
        out_specs=pl.BlockSpec((1, 1, C * G, hd),
                               lambda b, h, j, t, c: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((C * G, hd), jnp.float32),
            pltpu.VMEM((C * G, 1), jnp.float32),
            pltpu.VMEM((C * G, 1), jnp.float32),
        ],
    )
    kern = functools.partial(_fp_kernel, block=block, nb=bpr, C=C, G=G,
                             scale=scale, latent=latent)
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, C * G, hd), q.dtype),
        interpret=interpret,
    )(table, q_off, qg, *pools)
    return out.reshape(B, KV, C, G, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B, C, H, hd)
