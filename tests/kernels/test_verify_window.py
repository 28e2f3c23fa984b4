"""Speculative verify window vs single-token decode (DESIGN.md §8.4).

``flash_verify`` reuses the flash-prefill chunk kernel as the
speculative verifier: a k+1-token window whose first query sits at
``q_off = cur_len - 1``. Its contract is that position ``j`` of the
window scores EXACTLY like a single-token decode at depth
``q_off + j + 1`` — so the parity oracle here is ``paged_attention``
composed W times at successive depths, across window widths (including
ones whose ``W * G`` query tile needs sublane padding), arbitrary
per-row offsets, ragged block tails, GQA, and bf16 pools.

The gather-path analogue ``verify_attention`` must match composed
``decode_attention`` BITWISE — that is the scheduler's greedy
bit-identity mechanism (same full-width masked softmax, vectorized
over the window), asserted at rtol=atol=0.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_prefill.ops import flash_verify
from repro.kernels.paged_attention.ops import paged_attention

KEY = jax.random.PRNGKey(13)


def rand(shape, dtype, i=0):
    return jax.random.normal(jax.random.fold_in(KEY, i), shape
                             ).astype(dtype)


def _verify_case(B, W, H, KV, hd, block, bpr, dtype, i=0):
    """Random pool + SHUFFLED table + per-row window offsets covering
    the edges: offset 0 (empty history), a mid-block offset (window
    straddles a block seam), and the last window of a full row."""
    n_blocks = B * bpr + 3
    kp = rand((n_blocks, KV, block, hd), dtype, 10 + i)
    vp = rand((n_blocks, KV, block, hd), dtype, 20 + i)
    q = rand((B, W, H, hd), dtype, 30 + i)
    ids = jax.random.permutation(jax.random.fold_in(KEY, 40 + i), n_blocks)
    table = ids[:B * bpr].reshape(B, bpr).astype(jnp.int32)
    T = block * bpr
    off = jax.random.randint(jax.random.fold_in(KEY, 50 + i), (B,), 0,
                             max(T - W, 1)).astype(jnp.int32)
    off = off.at[0].set(0)
    off = off.at[B - 1].set(T - W)
    if B > 2:                       # ragged tail: window ends mid-block
        off = off.at[1].set(T - W - block // 2)
    return q, kp, vp, table, off


class TestFlashVerify:
    @pytest.mark.parametrize("B,W,H,KV,hd,block,bpr", [
        (3, 2, 4, 4, 32, 4, 5),    # k=1, MHA
        (2, 4, 8, 2, 64, 8, 3),    # k=3, GQA 4:1
        (3, 5, 6, 3, 16, 4, 4),    # k=4, GQA 2:1 (W*G=10: padded tile)
        (2, 9, 2, 1, 16, 16, 2),   # k=8, MQA (W*G=18: padded tile)
        (3, 8, 4, 2, 32, 4, 6),    # k=7, aligned tile (W*G=16)
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_composed_decode(self, B, W, H, KV, hd, block, bpr,
                                     dtype):
        """Window position j == a single-token paged decode at depth
        off + j + 1, for every j — the verify window is k+1 decodes
        fused into one pass."""
        q, kp, vp, table, off = _verify_case(B, W, H, KV, hd, block,
                                             bpr, dtype)
        out = flash_verify(q, kp, vp, table, off)
        assert out.shape == q.shape
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        for j in range(W):
            ref = paged_attention(q[:, j:j + 1], kp, vp, table,
                                  (off + j + 1).astype(jnp.int32))
            np.testing.assert_allclose(
                out[:, j:j + 1].astype(np.float32),
                ref.astype(np.float32), rtol=tol, atol=tol,
                err_msg=f"window position {j}")

    def test_unallocated_tail_blocks(self):
        """-1 table entries beyond each row's visible span clip to the
        drop/0 block on both paths; masking hides them either way."""
        B, W, block, bpr = 3, 4, 4, 4
        q, kp, vp, table, off = _verify_case(B, W, 4, 2, 16, block, bpr,
                                             jnp.float32, i=1)
        need = -(-(off + W) // block)
        keep = jnp.arange(table.shape[1])[None, :] < need[:, None]
        table = jnp.where(keep, table, -1)
        out = flash_verify(q, kp, vp, table, off)
        for j in range(W):
            ref = paged_attention(q[:, j:j + 1], kp, vp, table,
                                  (off + j + 1).astype(jnp.int32))
            np.testing.assert_allclose(out[:, j:j + 1], ref,
                                       rtol=2e-5, atol=2e-5)

    def test_pad_width_independence(self):
        """The same window through different sublane paddings (driven
        by W) gives identical leading positions: the pad queries are
        discarded, never mixed in."""
        B, H, KV, hd, block, bpr = 2, 6, 3, 16, 4, 5
        q, kp, vp, table, off = _verify_case(B, 8, H, KV, hd, block,
                                             bpr, jnp.float32, i=2)
        full = flash_verify(q, kp, vp, table, off)
        for W in (1, 3, 5):
            part = flash_verify(q[:, :W], kp, vp, table, off)
            np.testing.assert_allclose(part, full[:, :W],
                                       rtol=2e-6, atol=2e-6)


class TestVerifyAttentionGather:
    def test_bitwise_vs_composed_decode(self):
        """The XLA gather verify path IS the decode path vectorized
        over the window: write the window K/V once, then position j of
        ``verify_attention`` must equal ``decode_attention`` at
        ``cur_len = q_off + j + 1`` with rtol=atol=0. This is the
        greedy bit-identity mechanism — any drift here would flip
        near-tie argmaxes between speculative and sequential decode."""
        from repro.models import attention as attn_lib
        from repro.serve import kv_cache as kvc

        n, max_len, KV, hd, H, block, W = 3, 24, 2, 16, 6, 4, 5
        for impl in ("dense", "paged"):
            if impl == "paged":
                cache = kvc.PagedKVCache.create(1, n, max_len, KV, hd,
                                                jnp.bfloat16, block=block)
                cache = cache.alloc(jnp.arange(n, dtype=jnp.int32),
                                    jnp.full((n,), max_len, jnp.int32))
                view = cache.view_at(0)
            else:
                view = kvc.DenseView(
                    jnp.zeros((n, max_len, KV, hd), jnp.bfloat16),
                    jnp.zeros((n, max_len, KV, hd), jnp.bfloat16))
            hist_k = rand((n, max_len, KV, hd), jnp.bfloat16, 1)
            hist_v = rand((n, max_len, KV, hd), jnp.bfloat16, 2)
            view = view.write_prompt(hist_k, hist_v)
            q_off = jnp.asarray([0, 9, max_len - W], jnp.int32)
            kw = rand((n, W, KV, hd), jnp.bfloat16, 3)
            vw = rand((n, W, KV, hd), jnp.bfloat16, 4)
            q = rand((n, W, H, hd), jnp.bfloat16, 5)
            wview = view.write_chunk(kw, vw, q_off)
            out = attn_lib.verify_attention(q, wview, q_off=q_off,
                                            attn_impl="xla")
            for j in range(W):
                ref = attn_lib.decode_attention(
                    q[:, j:j + 1], wview, cur_len=q_off + j + 1,
                    attn_impl="xla")
                np.testing.assert_array_equal(
                    np.asarray(out[:, j:j + 1]), np.asarray(ref),
                    err_msg=f"{impl} window position {j}")
