"""Pallas TPU paged-attention decode kernel (GQA-aware, gather-free).

The vLLM PagedAttention design on TPU: single-token decode reads K/V
*through the block table* instead of first reconstructing the dense
``(rows, max_len, KV, hd)`` layout (which ``PagedView.gather`` pays per
layer per decode step — the transient the paged cache was supposed to
eliminate). The block table and per-row lengths ride in as
**scalar-prefetch** operands (``pltpu.PrefetchScalarGridSpec``): they
are resident in SMEM before the body runs, so the kernel can chase the
indirection itself. This is the paper's argument executed at the
memory system: data-dependent addressing stays on-device, inside the
compiled step.

One grid step per row, grid ``(B,)``. Within the step a loop runs over
the row's LIVE pages only, ``ceil(n_pages / P)`` times with ``n_pages
= ceil(cur_len[b] / block)``, P pages at a time (a chunk), and every
KV head of the row is computed on each chunk. The work per call thus
follows the rows' contexts, not the table's width: a short row costs
a short loop, and a row with ``cur_len`` 0 issues no DMA and writes
zeros.

Layout/behaviour contract (shared with ``ref.py`` and
``serve.kv_cache.PagedView``):

- pools are head-major ``(n_blocks, KV, block, hd)`` — one layer's
  slice of the cache's ``(L, n_blocks, ...)`` pool — and stay in HBM
  (``memory_space=pl.ANY``). Page ``p`` of row ``b`` is the contiguous
  ``(KV, block, hd)`` slab ``pool[table[b, p]]``: one manual DMA per
  page and pool brings all of its KV heads into a ``(2, P, KV, block,
  hd)`` VMEM buffer;
- double buffering: chunk ``c + 1``'s DMAs are started before chunk
  ``c`` is computed, into the other slot of the buffer;
- P comes from the shapes (``pages_per_chunk``): the largest P <=
  ``blocks_per_row`` whose K chunk is at most ``CHUNK_BYTES``;
- the final chunk's pages past the last live one clamp to it and are
  masked by position; pages past the row's last live page are never
  fetched, and a ``cur_len`` past the table's capacity reads every
  page once;
- ``table`` entries < 0 (unallocated) clip to physical block 0 and the
  garbage is masked by ``cur_len`` — same lanes the gather path masks;
- the math is ``models.attention.decode_attention``'s: K/V read in
  the pool's dtype and upcast, fp32 scores, fp32 softmax statistics,
  fp32 ``p`` in the PV product and an fp32 accumulator (online softmax
  per KV head across chunks), cast to ``q.dtype`` at the end.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# K bytes of one chunk: each of the K and V buffers holds two chunks
CHUNK_BYTES = 512 * 1024


def pages_per_chunk(bpr: int, KV: int, block: int, hd: int,
                    itemsize: int) -> int:
    """P: the largest page count <= ``bpr`` whose K chunk fits
    ``CHUNK_BYTES`` (at least one page)."""
    page = KV * block * hd * itemsize
    return max(1, min(bpr, CHUNK_BYTES // page))


def _pa_kernel(table_ref, cl_ref, q_ref, k_hbm, v_hbm, o_ref,
               k_buf, v_buf, sem, acc_ref, m_ref, l_ref, *,
               block: int, P: int, scale: float):
    """Grid: (B,). One row, all its KV heads, its live pages only."""
    b = pl.program_id(0)
    KV, hd = k_buf.shape[2], k_buf.shape[4]
    # a cur_len past the table's capacity reads every page once, as the
    # gather path does (and no table entry past the row's last)
    cur = jnp.minimum(cl_ref[b], table_ref.shape[1] * block)
    n_pages = (cur + block - 1) // block
    n_chunks = (n_pages + P - 1) // P
    last = jnp.maximum(n_pages - 1, 0)

    def page_copies(slot, i, c=0):
        # page i of chunk c: padding past the last live page re-reads it
        page = jnp.maximum(table_ref[b, jnp.minimum(c * P + i, last)], 0)
        return (pltpu.make_async_copy(k_hbm.at[page], k_buf.at[slot, i],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[page], v_buf.at[slot, i],
                                      sem.at[1, slot]))

    def start(c, slot):
        def one(i, carry):
            for cp in page_copies(slot, i, c):
                cp.start()
            return carry
        jax.lax.fori_loop(0, P, one, 0)

    def wait(slot):
        # every copy into a slot has one page's size: wait P of them
        def one(i, carry):
            for cp in page_copies(slot, 0):
                cp.wait()
            return carry
        jax.lax.fori_loop(0, P, one, 0)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(n_chunks > 0)
    def _first():
        start(0, 0)

    def chunk(c, carry):
        slot = c % 2

        @pl.when(c + 1 < n_chunks)
        def _next():
            start(c + 1, 1 - slot)

        wait(slot)
        pos = c * (P * block) + jax.lax.broadcasted_iota(
            jnp.int32, (1, P * block), 1)
        live = pos < cur                                   # ragged tail
        for h in range(KV):
            q = q_ref[0, h].astype(jnp.float32) * scale    # (G, hd)
            k = k_buf[slot, :, h].reshape(P * block, hd).astype(jnp.float32)
            v = v_buf[slot, :, h].reshape(P * block, hd).astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(live, s, NEG_INF)
            m_prev, l_prev = m_ref[h], l_ref[h]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_prev * corr + p.sum(axis=1, keepdims=True)
            m_ref[h] = m_new
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, n_chunks, chunk, 0)
    out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = out.astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, table, cur_len, *,
                    interpret: bool = True):
    """q: (B, 1, H, hd); k/v_pool: (n_blocks, KV, block, hd);
    table: (B, bpr) int32; cur_len: (B,) int32 -> (B, 1, H, hd)."""
    B, _, H, hd = q.shape
    KV, block = k_pool.shape[1], k_pool.shape[2]
    G = H // KV
    bpr = table.shape[1]
    scale = 1.0 / math.sqrt(hd)
    # The TPU compiler slices an HBM array only in whole 128-lane tiles,
    # so a narrower head is zero-padded to the lane width (a copy of the
    # layer's pool per call; exact: the zero lanes add nothing to a
    # score and come out as zeros, sliced off below).
    lanes = -(-hd // 128) * 128
    if lanes != hd:
        pad = [(0, 0)] * 3 + [(0, lanes - hd)]
        q, k_pool, v_pool = (jnp.pad(x, pad) for x in (q, k_pool, v_pool))
    P = pages_per_chunk(bpr, KV, block, lanes, k_pool.dtype.itemsize)
    qg = q.reshape(B, KV, G, lanes)
    table = jnp.asarray(table, jnp.int32)
    cur_len = jnp.asarray(cur_len, jnp.int32)
    row = pl.BlockSpec((1, KV, G, lanes), lambda b, t, c: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[row, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((2, P, KV, block, lanes), k_pool.dtype),
            pltpu.VMEM((2, P, KV, block, lanes), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((KV, G, lanes), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
        ],
    )
    kern = functools.partial(_pa_kernel, block=block, P=P, scale=scale)
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, lanes), q.dtype),
        interpret=interpret,
    )(table, cur_len, qg, k_pool, v_pool)
    return out[..., :hd].reshape(B, 1, H, hd)
