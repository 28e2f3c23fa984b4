"""Serving: prefill+decode consistency and the in-graph generate loop."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import model_zoo
from repro.serve import engine

KEY = jax.random.PRNGKey(3)

DECODER_ARCHS = [a for a in ARCH_IDS if a != "whisper-small"]


@pytest.mark.parametrize("arch", ["smollm-135m", "falcon-mamba-7b",
                                  "zamba2-1.2b", "dbrx-132b",
                                  "whisper-small", "internvl2-1b"])
def test_decode_matches_forward(arch):
    """prefill + decode_step logits == full forward logits at that pos."""
    cfg = get_config(arch, smoke=True)
    if cfg.moe is not None:
        # Capacity drops depend on how many tokens share a group: the
        # (S+1)-token forward may drop its last token's expert slot,
        # which one-token decode never does. Room for every token
        # (as in test_huge_capacity_equals_dense_topk) makes the
        # forward a reference for decode.
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = model_zoo.init_params(cfg, KEY)
    B, S = 2, 16
    tokens = jax.random.randint(KEY, (B, S + 1), 0, cfg.vocab)
    kwargs = {}
    batch = {"tokens": tokens}
    if cfg.family == "audio":
        kwargs["frames"] = jax.random.normal(
            KEY, (B, cfg.n_frames, cfg.d_model), jnp.bfloat16)
        batch["frames"] = kwargs["frames"]
    if cfg.family == "vlm":
        kwargs["prefix_embeds"] = jax.random.normal(
            KEY, (B, cfg.n_patches, cfg.d_model), jnp.bfloat16)
        batch["patches"] = kwargs["prefix_embeds"]

    logits_full, _ = model_zoo.forward(params, cfg, batch)

    prefix = cfg.n_patches if cfg.family == "vlm" else 0
    cache = engine.make_cache(cfg, B, S + prefix + 4)
    logits_pre, cache = engine.prefill(params, cfg, tokens[:, :S], cache,
                                       **kwargs)
    # prefill's last-position logits == forward at position S-1
    np.testing.assert_allclose(
        logits_pre[:, -1].astype(np.float32),
        logits_full[:, prefix + S - 1].astype(np.float32),
        rtol=5e-2, atol=5e-2)
    # decode one more token and compare against forward at position S
    logits_dec, _ = engine.decode_step(
        params, cfg, tokens[:, S:S + 1], cache,
        jnp.int32(S + prefix + 1))
    full_next, _ = model_zoo.forward(
        params, cfg, dict(batch, tokens=tokens))
    np.testing.assert_allclose(
        logits_dec[:, 0].astype(np.float32),
        full_next[:, prefix + S].astype(np.float32),
        rtol=5e-2, atol=5e-2)


def test_generate_early_exit():
    """The in-graph loop stops as soon as every sequence hits EOS."""
    cfg = get_config("smollm-135m", smoke=True)
    params = model_zoo.init_params(cfg, KEY)
    prompt = jax.random.randint(KEY, (2, 8), 2, cfg.vocab)

    res = engine.generate(params, cfg, prompt, max_new=12, eos_id=1)
    assert res.tokens.shape == (2, 12)
    assert int(res.steps) <= 12
    # force instant EOS: zero embeddings => all logits equal => argmax
    # is token 0; generate with eos_id=0 must exit after ~1 step
    params2 = dict(params)
    params2["embed"] = jnp.zeros_like(params["embed"])
    res2 = engine.generate(params2, cfg, prompt, max_new=12, eos_id=0)
    assert int(res2.steps) <= 3, f"early exit failed: {int(res2.steps)}"
    assert (res2.lengths <= 2).all()


def test_lengths_count_eos_and_text_lengths():
    """`lengths` includes the EOS token; `text_lengths` excludes it."""
    cfg = get_config("smollm-135m", smoke=True)
    params = model_zoo.init_params(cfg, KEY)
    prompt = jax.random.randint(KEY, (2, 8), 2, cfg.vocab)

    # forced instant EOS (zero embeddings => argmax = token 0)
    params0 = dict(params)
    params0["embed"] = jnp.zeros_like(params["embed"])
    res = engine.generate(params0, cfg, prompt, max_new=6, eos_id=0)
    # one emitted token (the EOS itself): lengths=1, text_lengths=0
    np.testing.assert_array_equal(np.asarray(res.lengths), [1, 1])
    np.testing.assert_array_equal(np.asarray(res.text_lengths), [0, 0])
    assert int(res.tokens[0, 0]) == 0        # tokens[:lengths] includes EOS

    # no EOS: lengths == text_lengths == max_new
    res2 = engine.generate(params, cfg, prompt, max_new=6, eos_id=-1)
    np.testing.assert_array_equal(np.asarray(res2.lengths), [6, 6])
    np.testing.assert_array_equal(np.asarray(res2.text_lengths), [6, 6])

    # the batch-sync reference agrees on both fields
    res3 = engine.generate_batch_sync(params0, cfg, prompt, max_new=6,
                                      eos_id=0)
    np.testing.assert_array_equal(np.asarray(res3.lengths), [1, 1])
    np.testing.assert_array_equal(np.asarray(res3.text_lengths), [0, 0])


@pytest.mark.parametrize("arch", ["zamba2-1.2b", "dbrx-132b",
                                  "whisper-small", "internvl2-1b"])
def test_paged_matches_dense_across_families(arch):
    """The paged KV cache is bit-identical to dense for every family
    with attention K/V — hybrid (shared-app cache), MoE, audio
    (encoder-decoder self-attn; cross stays dense), VLM (patch
    prefix)."""
    cfg = get_config(arch, smoke=True)
    params = model_zoo.init_params(cfg, KEY)
    B, S = 2, 8
    prompt = jax.random.randint(KEY, (B, S), 2, cfg.vocab)
    kwargs = {}
    if cfg.family == "audio":
        kwargs["frames"] = jax.random.normal(
            KEY, (B, cfg.n_frames, cfg.d_model), jnp.bfloat16)
    if cfg.family == "vlm":
        kwargs["prefix_embeds"] = jax.random.normal(
            KEY, (B, cfg.n_patches, cfg.d_model), jnp.bfloat16)
    dense = engine.generate_batch_sync(params, cfg, prompt, max_new=6,
                                       eos_id=1, **kwargs)
    paged = engine.generate_batch_sync(params, cfg, prompt, max_new=6,
                                       eos_id=1, kv_impl="paged",
                                       kv_block=4, **kwargs)
    np.testing.assert_array_equal(np.asarray(dense.tokens),
                                  np.asarray(paged.tokens))


def test_generate_matches_stepwise_decode():
    cfg = get_config("llama3.2-1b", smoke=True)
    params = model_zoo.init_params(cfg, KEY)
    B, S, NEW = 1, 8, 6
    prompt = jax.random.randint(KEY, (B, S), 2, cfg.vocab)
    res = engine.generate(params, cfg, prompt, max_new=NEW, eos_id=0)

    # manual loop
    cache = engine.make_cache(cfg, B, S + NEW + 1)
    logits, cache = engine.prefill(params, cfg, prompt, cache)
    tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
    toks = [int(tok[0, 0])]
    cur = S + 1
    for _ in range(NEW - 1):
        logits, cache = engine.decode_step(params, cfg, tok, cache,
                                           jnp.int32(cur))
        tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        toks.append(int(tok[0, 0]))
        cur += 1
    np.testing.assert_array_equal(np.asarray(res.tokens[0]),
                                  np.asarray(toks))
