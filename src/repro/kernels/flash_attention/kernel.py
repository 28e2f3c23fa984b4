"""Pallas TPU flash attention (forward), GQA-aware.

TPU adaptation of the FlashAttention insight (DESIGN.md §2): the online-
softmax accumulator lives in VMEM scratch; the kv-block dimension is the
innermost (sequential) grid axis so XLA streams K/V blocks HBM→VMEM
while the MXU consumes the previous block. Causal skipping is done with
``pl.when`` on whole blocks above the diagonal.

Operands are head-major, ``(B, H, S, D)``: a grid step's block is
``(1, 1, blk, D)``, so its last two dims are the array's own
``(S, D)`` tiles — the TPU compiler requires each of the last two block
dims to be a multiple of (8, 128) or the whole array dim, which a
``(blk, 1, D)`` slice of a token-major ``(B, S, H, D)`` array fails on
the head axis. ``ops.flash_attention`` takes the model's token-major
layout and transposes.

Block shapes default to (128, 128): the MXU is 128×128, so q/k tiles are
hardware-aligned; VMEM footprint per step is
q(128·D) + k(128·D) + v(128·D) + acc(128·D) + stats ≈ 4·128·D·4B ≈ 256KB
at D=128 — comfortably inside the ~16MB VMEM budget, leaving room for
double buffering.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
               blk_q: int, blk_k: int, nk: int, causal: bool, scale: float):
    """Grid: (B, H, nq, nk); nk innermost/sequential."""
    j = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale            # (blk_q, D)
        k = k_ref[0, 0].astype(jnp.float32)                    # (blk_k, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
        if causal:
            qpos = qi * blk_q + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 0)
            kpos = j * blk_k + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + p.sum(axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = (acc_ref[...] * corr
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v, (((1,), (0,)), ((), ()))))

    if causal:
        # whole block above the diagonal -> skip
        pl.when(j * blk_k <= qi * blk_q + blk_q - 1)(_compute)
    else:
        _compute()

    @pl.when(j == nk - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, blk_q: int = 128,
                    blk_k: int = 128, interpret: bool = True):
    """Head-major: q: (B, H, S, D); k/v: (B, KV, T, D) -> (B, H, S, D)."""
    B, H, S, D = q.shape
    KV, T = k.shape[1], k.shape[2]
    G = H // KV
    blk_q = min(blk_q, S)
    blk_k = min(blk_k, T)
    assert S % blk_q == 0 and T % blk_k == 0, (S, T, blk_q, blk_k)
    nq, nk = S // blk_q, T // blk_k
    scale = 1.0 / math.sqrt(D)

    grid = (B, H, nq, nk)
    kernel = functools.partial(_fa_kernel, blk_q=blk_q, blk_k=blk_k,
                               nk=nk, causal=causal, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, blk_k, D),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, blk_k, D),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, D), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
