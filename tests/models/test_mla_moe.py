"""Latent attention (MLA) over the paged latent pool and the dropless
expert-share MoE layer, against the plain float32 reference
(``bench/configs/mla_moe_decoder_ref.py``) at a small size on seeded
random weights, as the benchmark draws them.

The program runs in float32 here (``compute_dtype``), so it and the
reference differ only in the order of their sums: the absorbed against
the expanded attention, the kernels' online softmax against a whole
one, a grouped matmul over sorted entries against a dense one. That
is ~1e-6 on logits of std ~0.16, and every tolerance below (``TOL``)
is 1e-4: a hundred times that, and thousands of times under what a
planted fault moves (0.4-0.6: the shared expert dropped, the
correction bias ignored, or the reference's fp8 control in the
program's place), which ``test_planted_faults_fail`` sees fail.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mla_moe_setup import program, reference, small_config, weights

TOL = 1e-4
PLENS = (37, 20, 9)       # ragged last chunks of 16: 5, 4 and 9 tokens
DECODE = 6
CHUNK = 16


@pytest.fixture(scope="module")
def setup():
    c = small_config()
    return c, program(c), reference(c), weights(c)


def _tokens(c, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, c["vocab_size"],
                        (len(PLENS), max(PLENS) + DECODE)).astype(np.int32)


def served_logits(params, cfg, toks, plens=PLENS, kv_block=8, cache=None,
                  start=None):
    """Chunked prefill of each row's prompt through the paged latent
    pool, then ``DECODE`` decode steps feeding ``toks``' continuation:
    {position: logits} per row. ``start`` (per row) begins prefilling
    there, over a pool that already holds the positions before it."""
    from repro.serve import engine
    n, W = len(plens), max(plens)
    max_len = W + DECODE + 8
    if cache is None:
        cache = engine.make_cache(cfg, n, max_len, kv_impl="paged",
                                  kv_block=kv_block)
        cache["attn"] = cache["attn"].alloc(jnp.arange(n),
                                            jnp.full((n,), max_len))
    prompts = np.zeros((n, W), np.int32)
    for i, L in enumerate(plens):
        prompts[i, :L] = toks[i, :L]
    pl = np.asarray(plens)
    off = np.zeros(n, np.int32) if start is None else np.asarray(start)
    out = [dict() for _ in range(n)]
    while (off < pl).any():
        mask = off < pl
        pos = off[:, None] + np.arange(CHUNK)[None]
        valid = mask[:, None] & (pos < pl[:, None])
        lg, cache = engine.prefill_chunk(
            params, cfg, jnp.asarray(prompts), cache, jnp.asarray(off),
            chunk=CHUNK, mask=jnp.asarray(mask), valid=jnp.asarray(valid))
        lg = np.asarray(lg, np.float32)
        for i, j in zip(*np.nonzero(valid)):
            out[i][pos[i, j]] = lg[i, j]
        off = np.where(mask, off + CHUNK, off)
    for d in range(DECODE):
        tok = np.asarray([toks[i, L + d] for i, L in enumerate(plens)],
                         np.int32)[:, None]
        lg, cache = engine.decode_step(params, cfg, jnp.asarray(tok), cache,
                                       jnp.asarray(pl + 1 + d))
        for i, L in enumerate(plens):
            out[i][L + d] = np.asarray(lg, np.float32)[i, 0]
    return out, cache


def reference_logits(ref, params, c, toks, quant=None):
    out = []
    with jax.default_matmul_precision("highest"):
        for i, L in enumerate(PLENS):
            seq = np.zeros(256, np.int32)
            seq[:L + DECODE] = toks[i, :L + DECODE]
            h = ref.hidden(params, c, jnp.asarray(seq), quant=quant)
            out.append(np.asarray(ref.head(params, c, h))[:L + DECODE])
    return out


def widest_gap(got, want):
    return max(float(np.abs(row[p] - want[i][p]).max())
               for i, row in enumerate(got) for p in row)


@pytest.fixture(scope="module")
def ref_logits(setup):
    c, _, ref, params = setup
    return reference_logits(ref, params, c, _tokens(c))


@pytest.mark.parametrize("attn_impl", ["pallas", "xla"])
def test_chunked_then_decode_matches_reference(setup, ref_logits, attn_impl):
    """Variable prompt lengths with ragged last chunks, then decode,
    through the paged latent pool: the Pallas kernels (interpret mode
    here) and the gather path both agree with the reference's full
    forward at every prompt and decode position."""
    c, cfg, _, params = setup
    got, cache = served_logits(params, dataclasses.replace(
        cfg, attn_impl=attn_impl), _tokens(c))
    assert sum(len(r) for r in got) == sum(PLENS) + len(PLENS) * DECODE
    assert cache["attn"].v_pool is None            # the latent layout
    assert widest_gap(got, ref_logits) < TOL


@pytest.mark.parametrize("fault", ["no_shared_expert", "bias_ignored",
                                   "fp8"])
def test_planted_faults_fail(setup, ref_logits, fault):
    """Each fault moves the logits far past ``TOL``."""
    c, cfg, ref, params = setup
    toks = _tokens(c)
    if fault == "fp8":
        got = reference_logits(ref, params, c, toks, quant="fp8")
        got = [{p: row[p] for p in range(len(row))} for row in got]
    else:
        leaf = {"no_shared_expert": "shared_down",
                "bias_ignored": "e_score_correction_bias"}[fault]
        bad = jax.tree.map(lambda a: a, params)
        bad["moe"][leaf] = bad["moe"][leaf] * 0
        got, _ = served_logits(bad, cfg, toks)
    assert widest_gap(got, ref_logits) > 100 * TOL


def test_bf16_serving_within_rounding(setup):
    """The cell's dtypes (bf16 weights and compute) against the
    reference on the same bf16 weights. bf16 activations and latent
    rows move a position's logits by ~0.005; where the router's choice
    is a near-tie, bf16 flips an expert and that position moves by up
    to ~0.3 (3 of 84 positions here), which is why the served cell is
    judged on a mean over many tokens. So: the median position within
    0.02, and the fp8 control's median over five times as far."""
    _, _, ref, _ = setup
    c16 = small_config(param_dtype="bfloat16", compute_dtype="bfloat16")
    params, toks = weights(c16), _tokens(c16)
    got, _ = served_logits(params, program(c16), toks)
    want = reference_logits(ref, params, c16, toks)
    fp8 = reference_logits(ref, params, c16, toks, quant="fp8")

    def median_gap(rows):
        return np.median([np.abs(row[p] - want[i][p]).max()
                          for i, row in enumerate(rows) for p in
                          (row if isinstance(row, dict) else range(
                              len(row)))])
    assert median_gap(got) < 0.02
    assert median_gap(fp8) > 5 * median_gap(got)


def test_absorbed_matches_expanded(setup):
    """One layer's latent attention: the absorbed form reading the
    pool (a whole prompt as one chunk; gather and kernel) against the
    expanded form over the sequence's own K and V."""
    from repro.models import mla
    from repro.serve import kv_cache as kvc
    c, cfg, _, params = setup
    S = 24
    x = jax.random.normal(jax.random.PRNGKey(3), (2, S, cfg.d_model))
    lp = jax.tree.map(lambda a: a[1], params["layers"]["attn"])
    pos = jnp.broadcast_to(jnp.arange(S)[None], (2, S))
    full, _ = mla.mla_apply(lp, x, cfg, positions=pos, mode="full")
    for impl in ("xla", "pallas"):
        cache = kvc.make_kv_cache(cfg, 1, 2, 32, impl="paged", block=8)
        cache = cache.alloc(jnp.arange(2), jnp.full((2,), 32))
        view = cache.view(jax.tree.map(lambda a: a[0], cache.layers))
        got, _ = mla.mla_apply(lp, x, dataclasses.replace(
            cfg, attn_impl=impl), positions=pos, mode="chunk",
            kv_cache=view, chunk_off=jnp.zeros((2,), jnp.int32))
        assert float(jnp.abs(got - full).max()) < TOL


def test_expert_shares_add_up_to_the_whole_layer(setup):
    """All 8 shares of the router's 16 experts (2 each), run through
    the program's dropless layer, add up to the reference's uncut layer
    (every expert held), the shared experts counted once."""
    from repro.models import moe as moe_lib
    c, cfg, ref, params = setup
    whole = small_config(n_routed_experts=16, expert_offset=0)
    wp = weights(whole, seed=9)["moe"]
    mp = jax.tree.map(lambda a: a[0], wp)
    h = jax.random.normal(jax.random.PRNGKey(4), (40, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        want = ref.moe(mp, whole, h)
    total, routed, local = 0.0, 0, 0
    for s in range(8):
        part = small_config(n_routed_experts=2, expert_offset=2 * s)
        share = dict(mp, **{k: mp[k][2 * s:2 * s + 2]
                            for k in ("w_gate", "w_up", "w_down")})
        out, counts = moe_lib.dropless_moe(share, h[None], program(part))
        total = total + out[0]
        routed, local = int(counts["moe_routed"]), local + int(
            counts["moe_local"])
    shared = ref._swiglu(h, mp["shared_gate"], mp["shared_up"],
                         mp["shared_down"], None)
    assert float(jnp.abs(total - 7 * shared - want).max()) < TOL
    assert local == routed == 40 * c["num_experts_per_tok"]


def test_routing_by_bias_weighted_by_scores(setup):
    """Selection by sigmoid score + correction bias; weights are the
    chosen scores, normalised over the k, times routed_scaling_factor;
    the bias changes the choice on some tokens."""
    from repro.models import moe as moe_lib
    c, cfg, _, params = setup
    mp = jax.tree.map(lambda a: a[0], params["moe"])
    h = np.asarray(jax.random.normal(jax.random.PRNGKey(5),
                                     (64, cfg.d_model)), np.float32)
    w, idx = moe_lib.route(mp, jnp.asarray(h), cfg)
    s = 1 / (1 + np.exp(-h @ np.asarray(mp["router"], np.float64)))
    sel = s + np.asarray(mp["e_score_correction_bias"], np.float64)
    k = c["num_experts_per_tok"]
    want_idx = np.argsort(-sel, axis=1)[:, :k]
    np.testing.assert_array_equal(np.sort(np.asarray(idx), 1),
                                  np.sort(want_idx, 1))
    ws = np.take_along_axis(s, np.asarray(idx), 1)
    want_w = ws / ws.sum(1, keepdims=True) * c["routed_scaling_factor"]
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=1e-5)
    by_score = np.sort(np.argsort(-s, axis=1)[:, :k], 1)
    assert (by_score != np.sort(want_idx, 1)).any(1).mean() > 0.1


def test_softmax_routing_chooses_and_weighs_by_the_softmax(setup):
    """``router="softmax"`` (Qwen/DBRX-style): the top k of the softmax
    over the router's experts, renormalised over the k; no bias."""
    from repro.models import moe as moe_lib
    c, cfg, _, params = setup
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, router="softmax", routed_scale=1.0))
    mp = jax.tree.map(lambda a: a[0], params["moe"])
    h = np.asarray(jax.random.normal(jax.random.PRNGKey(6),
                                     (64, cfg.d_model)), np.float32)
    w, idx = moe_lib.route(mp, jnp.asarray(h), cfg)
    z = h @ np.asarray(mp["router"], np.float64)
    p = np.exp(z - z.max(1, keepdims=True))
    p /= p.sum(1, keepdims=True)
    k = c["num_experts_per_tok"]
    want_idx = np.argsort(-p, axis=1)[:, :k]
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    pk = np.take_along_axis(p, want_idx, 1)
    np.testing.assert_allclose(np.asarray(w), pk / pk.sum(1, keepdims=True),
                               rtol=1e-5)


def test_prefix_hit_serves_the_same_logits(setup):
    """Rows that map a cached prefix's latent blocks into their tables
    and prefill only the rest serve the logits of rows that prefilled
    it all."""
    from repro.serve import engine
    c, cfg, _, params = setup
    toks = _tokens(c)
    toks[1, :32] = toks[0, :32]                # a shared 32-token prefix
    plens = (37, 36)
    miss, cache = served_logits(params, cfg, toks[:2], plens=plens)
    table = np.asarray(cache["attn"].table)
    n, max_len = 2, max(plens) + DECODE + 8
    hit = engine.make_cache(cfg, n, max_len, kv_impl="paged", kv_block=8)
    hit["attn"] = dataclasses.replace(hit["attn"],
                                      k_pool=cache["attn"].k_pool)
    shared = np.full((n, table.shape[1]), -1, np.int32)
    shared[1, :4] = table[0, :4]               # row 1 maps row 0's prefix
    hit["attn"] = hit["attn"].alloc(jnp.arange(n), jnp.full((n,), max_len),
                                    shared=jnp.asarray(shared))
    got, _ = served_logits(params, cfg, toks[:2], plens=plens, cache=hit,
                           start=(0, 32))
    for p in range(32, plens[1] + DECODE):
        np.testing.assert_allclose(got[1][p], miss[1][p], atol=TOL)


def test_scheduler_serves_variable_lengths_and_counts(setup):
    """``DecodeScheduler`` (paged, chunked, Pallas) takes prompts of any
    length for the dropless layer, and its counters sum the routed and
    local entries of real tokens over the MoE layers."""
    from repro.serve import scheduler
    c, cfg, _, params = setup
    sched = scheduler.DecodeScheduler(
        params, cfg, n_slots=2, prompt_len=40, max_new_cap=5, kv="paged",
        kv_block=8, prefill="chunked", chunk_tokens=16)
    toks = _tokens(c)
    for i, L in enumerate(PLENS):
        sched.submit(toks[i:i + 1, :L], max_new=5)
    done = sched.run_until_drained()
    assert sorted(f.request_id for f in done) == [0, 1, 2]
    assert all(f.length == 5 for f in done)
    k = sched.counters()
    moe_layers = cfg.n_layers - cfg.first_k_dense
    assert k["moe_routed"] == (moe_layers * cfg.moe.top_k
                               * (sum(PLENS) + 5 * len(PLENS)))
    assert 0 < k["moe_local"] < k["moe_routed"]


def test_latent_pool_bytes():
    """A latent row per token and layer: 576 values stored in 640
    lanes, no V pool; at the cell's 14 layers 16128 B of values."""
    from repro.configs import get_config
    from repro.serve import kv_cache as kvc
    cfg = get_config("moonlight-16b-a3b")
    assert cfg.mla.row == 576 and cfg.mla.row_lanes == 640
    cache = kvc.make_kv_cache(cfg, 14, 4, 64, impl="paged", block=16,
                              abstract=True)
    assert cache.v_pool is None
    assert cache.k_pool.shape == (14, 16, 1, 16, 640)
    assert 14 * cfg.mla.row * 2 == 16128
