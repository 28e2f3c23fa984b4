"""Pallas TPU paged-attention decode kernel (GQA-aware, gather-free).

The vLLM PagedAttention design on TPU: single-token decode reads K/V
*through the block table* instead of first reconstructing the dense
``(rows, max_len, KV, hd)`` layout (which ``PagedView.gather`` pays per
layer per decode step — the transient the paged cache was supposed to
eliminate). The block table and per-row lengths ride in as
**scalar-prefetch** operands (``pltpu.PrefetchScalarGridSpec``): they
are resident in SMEM before the body runs, so the BlockSpec index maps
can chase the indirection — grid step ``(b, h, g, j)`` DMAs exactly
physical block ``table[b, j]`` of the shared pool HBM→VMEM, nothing
else (``g`` tiles wide GQA groups in 8-query-row accumulator tiles —
multi-query grid tiling, so G = 16 MQA decode no longer pads a whole
``(G, hd)`` fp32 scratch tile). This is the paper's argument executed
at the memory system: data-dependent addressing stays on-device,
inside the compiled step.

Layout/behaviour contract (shared with ``ref.py`` and
``serve.kv_cache.PagedView``):

- pools are head-major ``(n_blocks, KV, block, hd)`` — one layer's
  slice of the cache's ``(L, n_blocks, ...)`` pool. A grid step's K/V
  block is ``(1, 1, block, hd)``: its last two dims are the pool's own
  ``(block, hd)``, which the TPU compiler requires (each of the last
  two block dims must be a multiple of (8, 128) or the whole array
  dim; a token-major ``(block, 1, hd)`` slice fails that on the head
  axis);
- ``table`` entries < 0 (unallocated) clip to physical block 0 and the
  garbage is masked by ``cur_len`` — same lanes the gather path masks;
- blocks at or beyond ``ceil(cur_len/block)`` are clamped to the last
  valid block in the index map, so the sequential-grid pipeline elides
  their DMAs (same block index as the previous step ⇒ no copy) and
  ``pl.when`` skips their FLOPs;
- the online-softmax accumulator lives in VMEM scratch across the
  innermost (sequential) block axis, exactly like
  ``kernels.flash_attention``.

VMEM per step is q(G·hd) + k/v(block·hd) + acc ≈ a few KB — the win is
HBM traffic: ``cur_len[b]`` tokens per row instead of ``max_len``, and
zero dense-layout materialization.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _pa_kernel(table_ref, cl_ref, q_ref, k_ref, v_ref, o_ref,
               acc_ref, m_ref, l_ref, *, block: int, nb: int, scale: float):
    """Grid: (B, KV, n_gt, nb); nb innermost/sequential.

    Multi-query grid tiling: wide GQA groups (G > 8) are split into
    ``n_gt`` tiles of ``Gt <= 8`` query rows — each (b, h, g) grid
    slice owns its own ``(Gt, hd)`` accumulator, so the scratch tile
    matches the fp32 sublane quantum instead of padding a whole
    ``(G, hd)`` tile per step. The K/V index map ignores ``g``: within
    one (b, h) the sequential (g, j) sweep revisits each physical
    block once per tile with the same index on the j axis.
    """
    b, j = pl.program_id(0), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    cur = cl_ref[b]

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)                # (block, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        pos = j * block + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
        s = jnp.where(pos < cur, s, NEG_INF)               # ragged tail
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + p.sum(axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # whole block beyond the row's valid length -> skip the FLOPs (its
    # DMA was already elided by the clamped index map)
    pl.when(j * block < cur)(_compute)

    @pl.when(j == nb - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, table, cur_len, *,
                    interpret: bool = True):
    """q: (B, 1, H, hd); k/v_pool: (n_blocks, KV, block, hd);
    table: (B, bpr) int32; cur_len: (B,) int32 -> (B, 1, H, hd)."""
    B, _, H, hd = q.shape
    KV, block = k_pool.shape[1], k_pool.shape[2]
    G = H // KV
    bpr = table.shape[1]
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, KV, G, hd)
    table = jnp.asarray(table, jnp.int32)
    cur_len = jnp.asarray(cur_len, jnp.int32)
    # Multi-query grid tiling for wide GQA groups: G > 8 in one tile
    # just pads the fp32 accumulator past the sublane quantum, so split
    # the group dim over a grid axis in 8-row tiles (ragged widths keep
    # the single tile — a 12-row tile beats an 8+pad4 pair).
    Gt = 8 if (G > 8 and G % 8 == 0) else G
    n_gt = G // Gt

    def kv_map(b, h, g, j, table_ref, cl_ref):
        # Clamp past-the-end blocks to the last valid one: the pipeline
        # sees an unchanged block index and skips the DMA entirely.
        last = jnp.maximum((cl_ref[b] + block - 1) // block - 1, 0)
        jj = jnp.minimum(j, last)
        return (jnp.maximum(table_ref[b, jj], 0), h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, n_gt, bpr),
        in_specs=[
            pl.BlockSpec((1, 1, Gt, hd),
                         lambda b, h, g, j, t, c: (b, h, g, 0)),
            pl.BlockSpec((1, 1, block, hd), kv_map),
            pl.BlockSpec((1, 1, block, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, Gt, hd),
                               lambda b, h, g, j, t, c: (b, h, g, 0)),
        scratch_shapes=[
            pltpu.VMEM((Gt, hd), jnp.float32),
            pltpu.VMEM((Gt, 1), jnp.float32),
            pltpu.VMEM((Gt, 1), jnp.float32),
        ],
    )
    kern = functools.partial(_pa_kernel, block=block, nb=bpr, scale=scale)
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(table, cur_len, qg, k_pool, v_pool)
    return out.reshape(B, 1, H, hd)
