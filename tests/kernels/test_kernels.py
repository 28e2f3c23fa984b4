"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import attention_ref, flash_attention
from repro.kernels.flash_prefill.ops import flash_prefill, \
    flash_prefill_ref
from repro.kernels.lstm_cell.ops import lstm_cell, lstm_cell_ref
from repro.kernels.paged_attention import kernel as pa_kernel
from repro.kernels.paged_attention.ops import paged_attention, \
    paged_attention_ref
from repro.kernels.selective_scan.ops import selective_scan, \
    selective_scan_ref

KEY = jax.random.PRNGKey(7)


def rand(shape, dtype, i=0):
    return jax.random.normal(jax.random.fold_in(KEY, i), shape
                             ).astype(dtype)


class TestFlashAttention:
    @pytest.mark.parametrize("B,S,H,KV,D", [
        (1, 128, 4, 4, 32),    # MHA
        (2, 256, 8, 2, 64),    # GQA 4:1
        (1, 64, 6, 3, 128),    # GQA 2:1, wide head
        (2, 128, 2, 1, 16),    # MQA
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("causal", [True, False])
    def test_sweep(self, B, S, H, KV, D, dtype, causal):
        q = rand((B, S, H, D), dtype, 0)
        k = rand((B, S, KV, D), dtype, 1)
        v = rand((B, S, KV, D), dtype, 2)
        out = flash_attention(q, k, v, causal=causal, blk_q=64, blk_k=64)
        ref = attention_ref(q, k, v, causal=causal)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
        np.testing.assert_allclose(out.astype(np.float32),
                                   ref.astype(np.float32),
                                   rtol=tol, atol=tol)

    def test_block_shape_independence(self):
        q = rand((1, 256, 4, 32), jnp.float32, 0)
        k = rand((1, 256, 2, 32), jnp.float32, 1)
        v = rand((1, 256, 2, 32), jnp.float32, 2)
        outs = [flash_attention(q, k, v, causal=True, blk_q=bq, blk_k=bk)
                for bq, bk in [(64, 64), (128, 64), (64, 128), (128, 128)]]
        for o in outs[1:]:
            np.testing.assert_allclose(o, outs[0], rtol=1e-5, atol=1e-5)


def _paged_case(B, H, KV, hd, block, bpr, dtype, i=0, shuffle=True):
    """Random pool + SHUFFLED block table (the indirection must matter)
    + ragged cur_len including the cur_len=1 edge and a full row."""
    n_blocks = B * bpr + 3                     # spare blocks stay unused
    kp = rand((n_blocks, KV, block, hd), dtype, 10 + i)
    vp = rand((n_blocks, KV, block, hd), dtype, 20 + i)
    q = rand((B, 1, H, hd), dtype, 30 + i)
    ids = jax.random.permutation(jax.random.fold_in(KEY, 40 + i), n_blocks)
    if not shuffle:
        ids = jnp.arange(n_blocks)
    table = ids[:B * bpr].reshape(B, bpr).astype(jnp.int32)
    T = block * bpr
    cur = (1 + jax.random.randint(jax.random.fold_in(KEY, 50 + i),
                                  (B,), 0, T)).astype(jnp.int32)
    cur = cur.at[0].set(1)                     # single-token edge
    cur = cur.at[B - 1].set(T)                 # full (no ragged tail)
    if B > 2:
        cur = cur.at[1].set(T - block // 2)    # ragged last block
    return q, kp, vp, table, cur


class TestPagedAttention:
    @pytest.mark.parametrize("B,H,KV,hd,block,bpr", [
        (3, 4, 4, 32, 4, 5),     # MHA
        (2, 8, 2, 64, 8, 3),     # GQA 4:1
        (3, 6, 3, 16, 4, 4),     # GQA 2:1, ragged tail
        (2, 2, 1, 16, 16, 2),    # MQA, big blocks
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, B, H, KV, hd, block, bpr, dtype):
        q, kp, vp, table, cur = _paged_case(B, H, KV, hd, block, bpr, dtype)
        out = paged_attention(q, kp, vp, table, cur)
        ref = paged_attention_ref(q, kp, vp, table, cur)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(out.astype(np.float32),
                                   ref.astype(np.float32),
                                   rtol=tol, atol=tol)

    def test_matches_attention_ref_per_row(self):
        """Against the flash oracle: each row's single-token decode
        equals non-causal attention_ref over exactly its cur_len
        lanes of the linearized pool."""
        B, H, KV, hd, block, bpr = 3, 4, 2, 16, 4, 4
        q, kp, vp, table, cur = _paged_case(B, H, KV, hd, block, bpr,
                                            jnp.float32)
        out = paged_attention(q, kp, vp, table, cur)
        kg = kp[jnp.clip(table, 0)].swapaxes(2, 3).reshape(
            B, bpr * block, KV, hd)
        vg = vp[jnp.clip(table, 0)].swapaxes(2, 3).reshape(
            B, bpr * block, KV, hd)
        for b in range(int(B)):
            T = int(cur[b])
            ref = attention_ref(q[b:b + 1], kg[b:b + 1, :T],
                                vg[b:b + 1, :T], causal=False)
            np.testing.assert_allclose(out[b:b + 1], ref,
                                       rtol=2e-5, atol=2e-5)

    def test_unallocated_table_entries_match_gather_semantics(self):
        """-1 table entries clip to block 0 on BOTH paths; masked lanes
        make the result identical anyway."""
        q, kp, vp, table, cur = _paged_case(3, 4, 2, 16, 4, 4, jnp.float32)
        # drop each row's tail blocks beyond its cur_len
        need = -(-cur // 4)
        keep = jnp.arange(table.shape[1])[None, :] < need[:, None]
        table = jnp.where(keep, table, -1)
        out = paged_attention(q, kp, vp, table, cur)
        ref = paged_attention_ref(q, kp, vp, table, cur)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_block_size_independence(self):
        """The same logical K/V through different block sizes gives the
        same output (pools rebuilt per block size)."""
        B, H, KV, hd, T = 2, 4, 2, 32, 32
        k = rand((B, T, KV, hd), jnp.float32, 1)
        v = rand((B, T, KV, hd), jnp.float32, 2)
        q = rand((B, 1, H, hd), jnp.float32, 3)
        cur = jnp.asarray([T - 5, T], jnp.int32)
        outs = []
        for block in (4, 8, 16, 32):
            bpr = T // block
            kp = k.reshape(B * bpr, block, KV, hd).swapaxes(1, 2)
            vp = v.reshape(B * bpr, block, KV, hd).swapaxes(1, 2)
            table = jnp.arange(B * bpr, dtype=jnp.int32).reshape(B, bpr)
            outs.append(paged_attention(q, kp, vp, table, cur))
        for o in outs[1:]:
            np.testing.assert_allclose(o, outs[0], rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("B,H,KV,hd,block,bpr", [
        (3, 16, 1, 32, 4, 5),    # MQA, G=16: two sublane tiles of queries
        (2, 32, 2, 16, 8, 3),    # GQA 16:1 over 2 KV heads
        (2, 24, 2, 16, 4, 4),    # G=12: a ragged sublane tile
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_wide_gqa_multi_query_tiling(self, B, H, KV, hd, block, bpr,
                                         dtype):
        """Wide GQA groups (G > 8): a row's whole (G, hd) query of each
        KV head sits in one step; parity must hold past 8 query rows."""
        q, kp, vp, table, cur = _paged_case(B, H, KV, hd, block, bpr,
                                            dtype, i=3)
        out = paged_attention(q, kp, vp, table, cur)
        ref = paged_attention_ref(q, kp, vp, table, cur)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(out.astype(np.float32),
                                   ref.astype(np.float32),
                                   rtol=tol, atol=tol)

    @pytest.mark.parametrize("bpr,KV,block,hd,itemsize,want", [
        (128, 16, 16, 128, 2, 8),     # olmo-1b: 8 pages, 128 tokens
        (97, 4, 16, 128, 2, 32),      # qwen2-7b-pp4: 32 pages, 512 tokens
        (8, 3, 16, 128, 2, 8),        # hd 64 padded to 128 lanes: the row
        (3, 64, 64, 128, 4, 1),       # a page above the chunk: one page
    ])
    def test_pages_per_chunk_follows_the_shapes(self, bpr, KV, block, hd,
                                                itemsize, want):
        assert pa_kernel.pages_per_chunk(bpr, KV, block, hd,
                                         itemsize) == want

    @pytest.mark.parametrize("H,KV,block,bpr", [
        (16, 16, 16, 19),    # MHA, G 1: P 4 (fp32) / 8 (bf16)
        (28, 4, 16, 67),     # GQA 7:1: P 16 / 32
        (16, 1, 64, 67),     # MQA, G 16: P 16 / 32
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_chunk_edges(self, H, KV, block, bpr, dtype):
        """Live page counts at every chunk edge: none, a single page,
        below P, P, one past it, a multiple of P and one past that, the
        whole row (P does not divide it), with partial last pages,
        ``-1`` entries past the live pages and a shuffled table; and a
        row whose ``cur_len`` runs past the table, which reads every
        page once, as the reference does."""
        hd = 128
        P = pa_kernel.pages_per_chunk(bpr, KV, block, hd,
                                      jnp.dtype(dtype).itemsize)
        assert 1 < P < bpr and bpr % P
        pages = np.asarray(sorted({n for n in (0, 1, P - 1, P, P + 1,
                                               2 * P, 2 * P + 1, bpr)
                                   if n <= bpr}), np.int32)
        pages = np.append(pages, bpr)
        B = len(pages)
        n_blocks = B * bpr + 3
        kp = rand((n_blocks, KV, block, hd), dtype, 11)
        vp = rand((n_blocks, KV, block, hd), dtype, 21)
        q = rand((B, 1, H, hd), dtype, 31)
        ids = jax.random.permutation(jax.random.fold_in(KEY, 41), n_blocks)
        table = np.array(ids[:B * bpr].reshape(B, bpr), np.int32)
        table[np.arange(bpr)[None, :] >= pages[:, None]] = -1
        # every third row fills its last page; the others end inside it
        short = (np.arange(B) % 3) * (block // 3)
        cur = np.where(pages > 0, pages * block - short, 0).astype(np.int32)
        cur[-1] = (bpr + 1) * block + 3
        out = paged_attention(q, kp, vp, table, cur).astype(np.float32)
        ref = paged_attention_ref(q, kp, vp, table, cur).astype(np.float32)
        live = cur > 0
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(out[live], ref[live], rtol=tol, atol=tol)
        # an empty row issues no DMA and writes zeros
        np.testing.assert_array_equal(out[~live], 0)

    def test_matches_paged_view_gather_path(self):
        """Kernel vs the serving stack's own XLA gather path: the same
        PagedView, decode_attention with attn_impl pallas vs xla."""
        from repro.models import attention as attn_lib
        from repro.serve import kv_cache as kvc

        n, max_len, KV, hd, H, block = 3, 14, 2, 16, 4, 4
        cache = kvc.PagedKVCache.create(1, n, max_len, KV, hd, jnp.float32,
                                        block=block)
        cache = cache.alloc(jnp.arange(n, dtype=jnp.int32),
                            jnp.full((n,), max_len, jnp.int32))
        view = cache.view_at(0)
        k = rand((n, max_len, KV, hd), jnp.float32, 1)
        v = rand((n, max_len, KV, hd), jnp.float32, 2)
        view = view.write_prompt(k, v)
        q = rand((n, 1, H, hd), jnp.float32, 3)
        cur = jnp.asarray([1, 9, 14], jnp.int32)   # edge, ragged, full
        xla = attn_lib.decode_attention(q, view, cur_len=cur,
                                        attn_impl="xla")
        pal = attn_lib.decode_attention(q, view, cur_len=cur,
                                        attn_impl="pallas")
        np.testing.assert_allclose(pal, xla, rtol=2e-5, atol=2e-5)
        # a DenseView silently takes the gather path under "pallas"
        dense = kvc.DenseView(k, v)
        np.testing.assert_allclose(
            attn_lib.decode_attention(q, dense, cur_len=cur,
                                      attn_impl="pallas"),
            attn_lib.decode_attention(q, dense, cur_len=cur,
                                      attn_impl="xla"),
            rtol=0, atol=0)


class TestPagedAttentionEndToEnd:
    """Acceptance: greedy decode through the kernel (interpret mode on
    CPU) is bit-identical to the DenseKVCache reference."""

    ARCHS = ["smollm-135m",        # dense
             "dbrx-132b",          # moe
             "internvl2-1b",       # vlm
             "zamba2-1.2b",        # hybrid (shared-attn cache)
             "whisper-small"]      # audio (enc-dec self-attn decode)

    @pytest.mark.parametrize("arch", ARCHS)
    def test_generate_bit_identical(self, arch):
        from repro.configs import get_config
        from repro.models import model_zoo
        from repro.serve import engine

        cfg = get_config(arch, smoke=True)
        params = model_zoo.init_params(cfg, KEY)
        B, S = 2, 8
        prompt = jax.random.randint(KEY, (B, S), 2, cfg.vocab)
        kwargs = {}
        if cfg.family == "vlm":
            kwargs["prefix_embeds"] = jax.random.normal(
                KEY, (B, cfg.n_patches, cfg.d_model), jnp.bfloat16)
        if cfg.family == "audio":
            kwargs["frames"] = jax.random.normal(
                KEY, (B, cfg.n_frames, cfg.d_model), jnp.bfloat16)
        dense = engine.generate_batch_sync(params, cfg, prompt, max_new=6,
                                           eos_id=1, **kwargs)
        cfg_k = dataclasses.replace(cfg, attn_impl="pallas")
        kern = engine.generate_batch_sync(params, cfg_k, prompt, max_new=6,
                                          eos_id=1, kv_impl="paged",
                                          kv_block=4, **kwargs)
        assert dense.attn_impl == "xla-gather:dense"
        # interpret on CPU CI, compiled on a TPU host — both are the
        # kernel path and both must stay bit-identical
        assert kern.attn_impl.startswith("pallas-paged:")
        np.testing.assert_array_equal(np.asarray(dense.tokens),
                                      np.asarray(kern.tokens))
        np.testing.assert_array_equal(np.asarray(dense.lengths),
                                      np.asarray(kern.lengths))

    def test_scheduler_bit_identical_with_kernel(self):
        """Continuous batching with the kernel enabled: per-request
        greedy tokens equal the dense batch-synchronous reference even
        with queueing (mixed-depth neighbours in the pool)."""
        from repro.configs import get_config
        from repro.models import model_zoo
        from repro.serve import engine
        from repro.serve import scheduler as sched_lib

        cfg = get_config("smollm-135m", smoke=True)
        params = model_zoo.init_params(cfg, KEY)
        B, S, NEW = 3, 8, 8
        prompt = jax.random.randint(KEY, (B, S), 2, cfg.vocab)
        sync = engine.generate_batch_sync(params, cfg, prompt, max_new=NEW,
                                          eos_id=1)
        cfg_k = dataclasses.replace(cfg, attn_impl="pallas")
        sched = sched_lib.DecodeScheduler(params, cfg_k, n_slots=2,
                                          prompt_len=S, max_new_cap=NEW,
                                          eos_id=1, kv="paged", kv_block=4)
        assert sched.attn_impl.startswith("pallas-paged:")
        for b in range(B):
            sched.submit(prompt[b:b + 1], max_new=NEW, request_id=b)
        finished = sched.run_until_drained()
        assert len(finished) == B
        for f in finished:
            np.testing.assert_array_equal(
                f.tokens, np.asarray(sync.tokens[f.request_id, :f.length]))
        assert sched.free_blocks == sched.kv_blocks


def _prefill_case(B, C, H, KV, hd, block, bpr, dtype, i=0):
    """Random pool + SHUFFLED table + per-row chunk offsets covering
    the edges: offset 0 (first chunk), a mid-stream offset, and the
    last chunk of a full row."""
    n_blocks = B * bpr + 3
    kp = rand((n_blocks, KV, block, hd), dtype, 60 + i)
    vp = rand((n_blocks, KV, block, hd), dtype, 70 + i)
    q = rand((B, C, H, hd), dtype, 80 + i)
    ids = jax.random.permutation(jax.random.fold_in(KEY, 90 + i), n_blocks)
    table = ids[:B * bpr].reshape(B, bpr).astype(jnp.int32)
    T = block * bpr
    off = jax.random.randint(jax.random.fold_in(KEY, 95 + i), (B,), 0,
                             max(T - C, 1)).astype(jnp.int32)
    off = off.at[0].set(0)
    off = off.at[B - 1].set(T - C)
    return q, kp, vp, table, off


class TestFlashPrefill:
    @pytest.mark.parametrize("B,C,H,KV,hd,block,bpr", [
        (3, 4, 4, 4, 32, 4, 5),    # MHA
        (2, 8, 8, 2, 64, 8, 3),    # GQA 4:1
        (3, 5, 6, 3, 16, 4, 4),    # GQA 2:1, chunk not a block multiple
        (2, 1, 2, 1, 16, 16, 2),   # MQA, single-token chunk
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, B, C, H, KV, hd, block, bpr, dtype):
        q, kp, vp, table, off = _prefill_case(B, C, H, KV, hd, block, bpr,
                                              dtype)
        out = flash_prefill(q, kp, vp, table, off)
        ref = flash_prefill_ref(q, kp, vp, table, off)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(out.astype(np.float32),
                                   ref.astype(np.float32),
                                   rtol=tol, atol=tol)

    def test_matches_causal_attention_ref(self):
        """A full prompt written into the pool and prefilled in
        chunks equals one causal attention_ref pass over the prompt —
        for chunk sizes 1, the block size, and a non-divisor."""
        B, S, H, KV, hd, block = 2, 16, 4, 2, 16, 4
        k = rand((B, S, KV, hd), jnp.float32, 1)
        v = rand((B, S, KV, hd), jnp.float32, 2)
        q = rand((B, S, H, hd), jnp.float32, 3)
        ref = attention_ref(q, k, v, causal=True)
        bpr = S // block
        kp = k.reshape(B * bpr, block, KV, hd).swapaxes(1, 2)
        vp = v.reshape(B * bpr, block, KV, hd).swapaxes(1, 2)
        table = jnp.arange(B * bpr, dtype=jnp.int32).reshape(B, bpr)
        for C in (1, block, 5):
            outs = []
            for off in range(0, S, C):
                w = min(C, S - off)
                qc = jnp.zeros((B, C, H, hd)).at[:, :w].set(
                    q[:, off:off + w])
                o = flash_prefill(qc, kp, vp, table,
                                  jnp.full((B,), off, jnp.int32))
                outs.append(o[:, :w])
            out = jnp.concatenate(outs, axis=1)
            np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_unallocated_tail_blocks_match_ref(self):
        """-1 table entries past the visible window clip to block 0 on
        both paths; causal masking makes the result identical."""
        q, kp, vp, table, off = _prefill_case(3, 4, 4, 2, 16, 4, 4,
                                              jnp.float32, i=1)
        C = q.shape[1]
        need = -(-(off + C) // 4)
        keep = jnp.arange(table.shape[1])[None, :] < need[:, None]
        table = jnp.where(keep, table, -1)
        out = flash_prefill(q, kp, vp, table, off)
        ref = flash_prefill_ref(q, kp, vp, table, off)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_matches_prefill_attention_gather_path(self):
        """Kernel vs the serving stack's own XLA fallback: the same
        PagedView, prefill_attention with attn_impl pallas vs xla."""
        from repro.models import attention as attn_lib
        from repro.serve import kv_cache as kvc

        n, max_len, KV, hd, H, block, C = 3, 18, 2, 16, 4, 4, 5
        cache = kvc.PagedKVCache.create(1, n, max_len, KV, hd, jnp.float32,
                                        block=block)
        cache = cache.alloc(jnp.arange(n, dtype=jnp.int32),
                            jnp.full((n,), max_len, jnp.int32))
        view = cache.view_at(0)
        k = rand((n, max_len, KV, hd), jnp.float32, 1)
        v = rand((n, max_len, KV, hd), jnp.float32, 2)
        view = view.write_prompt(k, v)
        q = rand((n, C, H, hd), jnp.float32, 3)
        off = jnp.asarray([0, 7, max_len - C], jnp.int32)
        xla = attn_lib.prefill_attention(q, view, q_off=off,
                                         attn_impl="xla")
        pal = attn_lib.prefill_attention(q, view, q_off=off,
                                         attn_impl="pallas")
        np.testing.assert_allclose(pal, xla, rtol=2e-5, atol=2e-5)
        # a DenseView silently takes the gather path under "pallas"
        dense = kvc.DenseView(k, v)
        np.testing.assert_allclose(
            attn_lib.prefill_attention(q, dense, q_off=off,
                                       attn_impl="pallas"),
            attn_lib.prefill_attention(q, dense, q_off=off,
                                       attn_impl="xla"),
            rtol=0, atol=0)


class TestSelectiveScan:
    @pytest.mark.parametrize("B,Q,Di,N,blk", [
        (1, 16, 32, 8, 32),
        (2, 32, 64, 16, 32),
        (2, 64, 128, 4, 64),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, B, Q, Di, N, blk, dtype):
        dt = jax.nn.softplus(rand((B, Q, Di), dtype, 0))
        A = -jnp.exp(rand((Di, N), jnp.float32, 1) * 0.5)
        B_ = rand((B, Q, N), dtype, 2)
        C_ = rand((B, Q, N), dtype, 3)
        x = rand((B, Q, Di), dtype, 4)
        h0 = rand((B, Di, N), jnp.float32, 5)
        y, h = selective_scan(dt, A, B_, C_, x, h0, blk_d=blk)
        yr, hr = selective_scan_ref(dt, A, B_, C_, x, h0)
        tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
        np.testing.assert_allclose(y.astype(np.float32), yr,
                                   rtol=tol, atol=tol)
        np.testing.assert_allclose(h, hr, rtol=tol, atol=tol)


class TestLSTMCell:
    @pytest.mark.parametrize("B,D,H,bb,bh", [
        (8, 32, 64, 4, 32),
        (16, 64, 128, 8, 64),
        (4, 16, 32, 4, 32),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, B, D, H, bb, bh, dtype):
        w = rand((D + H, 4 * H), dtype, 0) * 0.1
        b = rand((4 * H,), dtype, 1) * 0.1
        x = rand((B, D), dtype, 2)
        c = rand((B, H), dtype, 3)
        h = rand((B, H), dtype, 4)
        cn, hn = lstm_cell(w, b, x, c, h, blk_b=bb, blk_h=bh)
        cr, hr = lstm_cell_ref(w, b, x, c, h)
        tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(cn.astype(np.float32),
                                   cr.astype(np.float32), rtol=tol, atol=tol)
        np.testing.assert_allclose(hn.astype(np.float32),
                                   hr.astype(np.float32), rtol=tol, atol=tol)

    def test_matches_model_cell(self):
        """The kernel is a drop-in for repro.models.rnn.lstm_cell."""
        from repro.models import rnn
        p = rnn.lstm_init(KEY, 32, 64)
        x = rand((8, 32), jnp.float32, 1)
        c = rand((8, 64), jnp.float32, 2)
        h = rand((8, 64), jnp.float32, 3)
        y_ref, (c_ref, h_ref) = rnn.lstm_cell(p, x, (c, h))
        c_k, h_k = lstm_cell(p["w"], p["b"], x, c, h, blk_b=8, blk_h=64)
        np.testing.assert_allclose(c_k, c_ref, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(h_k, h_ref, rtol=1e-5, atol=1e-5)
