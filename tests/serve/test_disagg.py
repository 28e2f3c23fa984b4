"""Prefill/decode disaggregation: bit-identity, shipping, slices.

The two-tier router (``repro.serve.disagg``) must be a pure
*placement* change: greedy decode through prefill-slice admission +
KV-block shipping + decode-slice splice is bit-identical to the
colocated chunked scheduler across dense/MoE/VLM, with prefix caching
and priority preemption composing unchanged. Tier-1 runs everything
mesh-less (both tiers on the default device — the ship/splice path is
fully exercised); the explicit 4+4 submesh split runs in an 8-device
subprocess (and in CI's 8-virtual-device job).

These identities are asserted on CPU devices. On TPU they hold when
each tier runs the colocated per-chip program (one chip, or rows
replicated); a tier that splits rows over several chips does not yet
reproduce colocated tokens (DESIGN.md §8.7), which
``chip_smoke.py --chips 4`` checks on the chip.
"""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model_zoo
from repro.serve import disagg as disagg_lib
from repro.serve import engine
from repro.serve import kv_cache as kvc
from repro.serve import scheduler as sched_lib
from repro.serve import speculative as spec_lib

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "dist"))
from dist_utils import run_ndev  # noqa: E402

KEY = jax.random.PRNGKey(3)


@pytest.fixture(scope="module")
def smollm():
    cfg = get_config("smollm-135m", smoke=True)
    return cfg, model_zoo.init_params(cfg, KEY)


def _tokens_by_rid(finished):
    return {f.request_id: np.asarray(f.tokens) for f in finished}


def _colocated(params, cfg, prompts, *, max_new=6, prompt_len=16,
               n_slots=2, prefix_cache=False, prefix_len=0,
               prefix_embeds=None):
    sched = sched_lib.DecodeScheduler(
        params, cfg, n_slots=n_slots, prompt_len=prompt_len,
        max_new_cap=max_new, eos_id=1, kv="paged", kv_block=4,
        prefill="chunked", chunk_tokens=5, prefix_cache=prefix_cache,
        prefix_len=prefix_len)
    for b, p in enumerate(prompts):
        sched.submit(p, max_new=max_new, request_id=b,
                     prefix_embeds=(prefix_embeds[b:b + 1]
                                    if prefix_len else None))
    return _tokens_by_rid(sched.run_until_drained()), sched


def _disagg(params, cfg, prompts, *, max_new=6, prompt_len=16,
            n_decode_slots=2, prefix_cache=False, prefix_len=0,
            prefix_embeds=None, speculative=None, **kw):
    d = disagg_lib.DisaggScheduler(
        params, cfg, n_prefill_slots=2, n_decode_slots=n_decode_slots,
        prompt_len=prompt_len, max_new_cap=max_new, eos_id=1,
        kv_block=4, chunk_tokens=5, prefix_cache=prefix_cache,
        prefix_len=prefix_len, speculative=speculative, **kw)
    for b, p in enumerate(prompts):
        d.submit(p, max_new=max_new, request_id=b,
                 prefix_embeds=(prefix_embeds[b:b + 1]
                                if prefix_len else None))
    return _tokens_by_rid(d.run_until_drained()), d


# ------------------- wire format (export/import) ----------------------------

def test_export_import_roundtrip(smollm):
    """export_rows -> import_rows into a second pool moves the exact
    K/V bits of every live block (dead tail columns ship as zeros and
    must not clobber anything the receiver later writes)."""
    cfg, _ = smollm
    rows, max_len, block = 3, 16, 4
    key = engine.kv_key(cfg)
    src = engine.make_cache(cfg, rows, max_len, kv_impl="paged",
                            kv_block=block)[key]
    lens = jnp.asarray([16, 7, 12], jnp.int32)
    src = src.alloc(jnp.arange(rows, dtype=jnp.int32), lens)
    src = dataclasses.replace(
        src,
        k_pool=jax.random.normal(KEY, src.k_pool.shape,
                                 src.k_pool.dtype),
        v_pool=jax.random.normal(jax.random.fold_in(KEY, 1),
                                 src.v_pool.shape, src.v_pool.dtype))
    n_cols = kvc.blocks_needed(max_len, block)
    k, v = src.export_rows(jnp.arange(rows, dtype=jnp.int32), n_cols)
    assert k.shape == (src.k_pool.shape[0], rows, n_cols,
                       src.k_pool.shape[2], block, src.k_pool.shape[4])

    dst = engine.make_cache(cfg, rows, max_len, kv_impl="paged",
                            kv_block=block)[key]
    dst = dst.alloc(jnp.arange(rows, dtype=jnp.int32), lens)
    dst = dst.import_rows(jnp.arange(rows, dtype=jnp.int32), k, v)
    k2, v2 = dst.export_rows(jnp.arange(rows, dtype=jnp.int32), n_cols)
    # live columns round-trip bit-for-bit; dead columns are zero on
    # both sides by construction
    np.testing.assert_array_equal(np.asarray(k), np.asarray(k2))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v2))
    # row 1 holds ceil((7+1)/4)=2 columns; its third must be dead zeros
    np.testing.assert_array_equal(np.asarray(k[:, 1, 2]),
                                  np.zeros_like(np.asarray(k[:, 1, 2])))


def test_import_rows_masked_rows_untouched(smollm):
    cfg, _ = smollm
    rows, max_len, block = 2, 8, 4
    key = engine.kv_key(cfg)
    cache = engine.make_cache(cfg, rows, max_len, kv_impl="paged",
                              kv_block=block)[key]
    cache = cache.alloc(jnp.arange(rows, dtype=jnp.int32),
                        jnp.full((rows,), max_len, jnp.int32))
    before = np.asarray(cache.k_pool)
    n_cols = kvc.blocks_needed(max_len, block)
    k = jnp.ones((cache.k_pool.shape[0], rows, n_cols,
                  cache.k_pool.shape[2], block, cache.k_pool.shape[4]),
                 cache.k_pool.dtype)
    out = cache.import_rows(jnp.arange(rows, dtype=jnp.int32), k, k,
                            mask=jnp.asarray([True, False]))
    after = np.asarray(out.k_pool)
    t = np.asarray(cache.table)
    np.testing.assert_array_equal(after[:, t[1, :n_cols]],
                                  before[:, t[1, :n_cols]])
    assert np.all(after[:, t[0, :n_cols]] == 1.0)


# ------------------- slice-mesh helpers -------------------------------------

def test_carve_slices_validation():
    from repro.dist import sharding as sh
    devs = jax.devices()
    with pytest.raises(ValueError):
        sh.carve_slices(0, devs)
    with pytest.raises(ValueError):
        sh.carve_slices(len(devs), devs)


def test_init_distributed_single_process_fallback(monkeypatch):
    from repro.launch import distributed as dist_env
    for k in ("JAX_COORDINATOR_ADDRESS", "COORDINATOR_ADDRESS"):
        monkeypatch.delenv(k, raising=False)
    assert dist_env.init_distributed() is False
    assert dist_env.is_multi_process() is False


# ------------------- bit-identity (dense) -----------------------------------

def test_disagg_bit_identity_dense(smollm):
    """Mixed-length prompts through the two-tier path == the colocated
    chunked scheduler, token for token, and the report string names
    the transfer path."""
    cfg, params = smollm
    prompts = [jax.random.randint(jax.random.fold_in(KEY, b), (1, L),
                                  2, cfg.vocab)
               for b, L in enumerate((3, 5, 9, 16, 1, 12))]
    ref, co = _colocated(params, cfg, prompts)
    got, d = _disagg(params, cfg, prompts)
    assert got.keys() == ref.keys()
    for rid in ref:
        np.testing.assert_array_equal(got[rid], ref[rid])
    assert co.transfer_impl == "colocated"
    assert d.transfer_impl == "device_put:ics"
    assert d.transfers == len(prompts)
    assert d.transfer_bytes > 0
    assert d.replay_mismatches == 0
    # both tiers fully drained: every block returned to its free-list
    assert d.prefill.free_blocks == d.prefill.kv_blocks
    assert d.decode.free_blocks == d.decode.kv_blocks


@pytest.mark.parametrize("arch", ["dbrx-132b", "internvl2-1b"])
def test_disagg_bit_identity_moe_vlm(arch):
    """MoE routing and VLM patch prefixes ride the shipment unchanged:
    the decode tier receives `plen = prompt + prefix` positions and
    reproduces the colocated stream exactly."""
    cfg = get_config(arch, smoke=True)
    params = model_zoo.init_params(cfg, KEY)
    B, S, NEW = 3, 8, 6
    prompt = jax.random.randint(KEY, (B, S), 2, cfg.vocab)
    prompts = [prompt[b:b + 1] for b in range(B)]
    prefix_len, embeds = 0, None
    if cfg.family == "vlm":
        prefix_len = cfg.n_patches
        embeds = jax.random.normal(KEY, (B, cfg.n_patches, cfg.d_model),
                                   jnp.bfloat16)
    ref, _ = _colocated(params, cfg, prompts, max_new=NEW, prompt_len=S,
                        prefix_len=prefix_len, prefix_embeds=embeds)
    got, d = _disagg(params, cfg, prompts, max_new=NEW, prompt_len=S,
                     prefix_len=prefix_len, prefix_embeds=embeds)
    assert got.keys() == ref.keys()
    for rid in ref:
        np.testing.assert_array_equal(got[rid], ref[rid])
    assert d.transfers == B


# ------------------- composition: prefix cache ------------------------------

def test_disagg_prefix_cache_bit_identity(smollm):
    """Prefix caching lives on the PREFILL tier: warm repeats of a hot
    prompt map cached blocks into their row and skip prefill work; the
    decode tier still receives a private fresh copy over the wire (no
    CoW crosses the slice boundary), and the streams stay identical."""
    cfg, params = smollm
    a = jax.random.randint(jax.random.fold_in(KEY, 0), (1, 16), 2,
                           cfg.vocab)
    b = jax.random.randint(jax.random.fold_in(KEY, 1), (1, 16), 2,
                           cfg.vocab)
    prompts = [a, b, a, a, b]
    ref, _ = _colocated(params, cfg, prompts, prefix_cache=True)
    got, d = _disagg(params, cfg, prompts, prefix_cache=True)
    assert got.keys() == ref.keys()
    for rid in ref:
        np.testing.assert_array_equal(got[rid], ref[rid])
    assert d.prefill.prefix_hit_blocks > 0
    assert d.decode.prefix_hit_blocks == 0


# ------------------- composition: speculative decode ------------------------

def test_disagg_speculative_bit_identity(smollm):
    """The n-gram drafter composes on the decode tier: the spliced
    row's prompt registers verbatim, so drafts look the continuation
    up exactly as a colocated slot would — output equals the plain
    (non-speculative) colocated stream and drafts actually fire."""
    cfg, params = smollm
    base = jax.random.randint(KEY, (1, 4), 2, cfg.vocab)
    prompt = jnp.tile(base, (1, 4))          # self-repeating: ngram hits
    spec = spec_lib.SpecConfig(k=3, drafter="ngram", ngram=2)
    ref, _ = _colocated(params, cfg, [prompt], max_new=8)
    got, d = _disagg(params, cfg, [prompt], max_new=8,
                     speculative=spec)
    np.testing.assert_array_equal(got[0], ref[0])
    assert d.decode.drafted_tokens > 0


# ------------------- composition: priority preemption -----------------------

def test_disagg_preemption_replay_bit_identical(smollm):
    """An urgent shipment that cannot fit evicts a strictly-lower-
    priority decode resident (the SLO plan, on the decode tier); the
    victim recomputes through the PREFILL tier and its replayed stream
    matches the preemption snapshot bit-for-bit — and every request
    still ends bit-identical to its uncontended greedy reference."""
    cfg, params = smollm
    prompts = [jax.random.randint(jax.random.fold_in(KEY, b), (1, 16),
                                  2, cfg.vocab) for b in range(3)]
    d = disagg_lib.DisaggScheduler(
        params, cfg, n_prefill_slots=1, n_decode_slots=2,
        prompt_len=16, max_new_cap=16, eos_id=1, kv_block=4,
        chunk_tokens=5, decode_kv_blocks=14, segment_steps=2,
        prefill_segment_steps=4)
    # two batch-class residents fill the decode tier exactly
    # (7 blocks each of 14)
    d.submit(prompts[0], max_new=8, request_id=0, priority=1)
    d.submit(prompts[1], max_new=8, request_id=1, priority=1)
    done = []
    for _ in range(3):
        done += d.step(max_steps=2)
    assert d.decode.active_count == 2 and not done
    # urgent arrival: no free slot, no free blocks -> must preempt
    d.submit(prompts[2], max_new=4, request_id=2, priority=0)
    done += d.run_until_drained()
    got = _tokens_by_rid(done)
    assert d.preemptions >= 1
    assert d.replay_mismatches == 0
    assert got.keys() == {0, 1, 2}
    for rid, max_new in ((0, 8), (1, 8), (2, 4)):
        ref = engine.generate_batch_sync(params, cfg, prompts[rid],
                                         max_new=max_new, eos_id=1)
        np.testing.assert_array_equal(
            got[rid], np.asarray(ref.tokens[0, :len(got[rid])]))


# ------------------- static guarantee ---------------------------------------

def _dense_kv_eqns(fn, args, *, rows, max_len, kv, hd):
    """Count jaxpr intermediates shaped like a dense KV tensor
    ``(rows, T >= max_len, kv, hd)`` (the layout disaggregation must
    never materialize on the decode slice)."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    hits = 0

    def walk(jx):
        nonlocal hits
        for eqn in jx.eqns:
            for v in eqn.outvars:
                s = getattr(v.aval, "shape", ())
                if (len(s) == 4 and s[0] == rows and s[1] >= max_len
                        and s[2] == kv and s[3] == hd):
                    hits += 1
            for p in eqn.params.values():
                if hasattr(p, "jaxpr"):
                    walk(p.jaxpr)
                elif isinstance(p, (list, tuple)):
                    for q in p:
                        if hasattr(q, "jaxpr"):
                            walk(q.jaxpr)
    walk(jaxpr.jaxpr)
    return hits


def test_ship_path_never_materializes_dense_kv(smollm):
    """The export -> wire -> import path stays block-granular end to
    end: walking its jaxpr finds ZERO dense ``(rows, max_len, KV, hd)``
    intermediates (a deliberately densified wire buffer IS found —
    detector sanity)."""
    cfg, _ = smollm
    rows, max_len, block = 4, 32, 4
    key = engine.kv_key(cfg)
    cache = engine.make_cache(cfg, rows, max_len, kv_impl="paged",
                              kv_block=block)[key]
    cache = cache.alloc(jnp.arange(rows, dtype=jnp.int32),
                        jnp.full((rows,), max_len, jnp.int32))
    n_cols = kvc.blocks_needed(max_len, block)
    kvh, hd = cache.k_pool.shape[2], cache.k_pool.shape[4]
    r = jnp.arange(rows, dtype=jnp.int32)

    def ship(src, dst):
        k, v = src.export_rows(r, n_cols)
        return dst.import_rows(r, k, v).k_pool

    assert _dense_kv_eqns(ship, (cache, cache), rows=rows,
                          max_len=max_len, kv=kvh, hd=hd) == 0
    assert _dense_kv_eqns(
        lambda s: (s.export_rows(r, n_cols)[0][0].swapaxes(2, 3)
                   .reshape(rows, n_cols * block, kvh, hd)),
        (cache,), rows=rows, max_len=max_len, kv=kvh, hd=hd) > 0


# ------------------- 8-device submesh split ---------------------------------

def test_disagg_4plus4_submesh_split():
    """The real thing: 4 prefill devices + 4 decode devices carved
    from one 8-device fleet. Pools live on provably disjoint devices,
    every request crosses the wire, and the stream is bit-identical
    to the mesh-less colocated reference."""
    out = run_ndev("""
from repro.configs import get_config
from repro.models import model_zoo
from repro.dist import sharding as sh
from repro.serve import scheduler as sched_lib
from repro.serve import disagg as disagg_lib

cfg = get_config("smollm-135m", smoke=True)
KEY = jax.random.PRNGKey(3)
params = model_zoo.init_params(cfg, KEY)
pf_dev, de_dev = sh.carve_slices(4)
assert len(pf_dev) == 4 and len(de_dev) == 4
pf_mesh, de_mesh = sh.slice_mesh(pf_dev), sh.slice_mesh(de_dev)
assert set(pf_mesh.devices.flat).isdisjoint(set(de_mesh.devices.flat))

prompts = [jax.random.randint(jax.random.fold_in(KEY, b), (1, L), 2,
                              cfg.vocab)
           for b, L in enumerate((3, 5, 9, 16, 1, 12))]
co = sched_lib.DecodeScheduler(
    params, cfg, n_slots=2, prompt_len=16, max_new_cap=6, eos_id=1,
    kv="paged", kv_block=4, prefill="chunked", chunk_tokens=5)
for b, p in enumerate(prompts):
    co.submit(p, max_new=6, request_id=b)
ref = {f.request_id: np.asarray(f.tokens)
       for f in co.run_until_drained()}

d = disagg_lib.DisaggScheduler(
    params, cfg, n_prefill_slots=2, n_decode_slots=2, prompt_len=16,
    max_new_cap=6, eos_id=1, prefill_mesh=pf_mesh, decode_mesh=de_mesh,
    kv_block=4, chunk_tokens=5)
for b, p in enumerate(prompts):
    d.submit(p, max_new=6, request_id=b)
got = {f.request_id: np.asarray(f.tokens)
       for f in d.run_until_drained()}

assert got.keys() == ref.keys()
for rid in ref:
    np.testing.assert_array_equal(got[rid], ref[rid])
kv_key = d.prefill._kv_key
pf_ids = {dv.id for dv in d.prefill.pool.cache[kv_key].k_pool.devices()}
de_ids = {dv.id for dv in d.decode.pool.cache[kv_key].k_pool.devices()}
assert pf_ids and de_ids and pf_ids.isdisjoint(de_ids), (pf_ids, de_ids)
assert pf_ids <= {dv.id for dv in pf_dev}
assert de_ids <= {dv.id for dv in de_dev}
assert d.transfers == len(prompts) and d.transfer_bytes > 0
assert d.transfer_impl == "device_put:ics"
print("DISAGG_8DEV_OK")
""")
    assert "DISAGG_8DEV_OK" in out


def test_disagg_2plus2_pallas_kernels_split_rows():
    """Pallas kernels (interpret mode here) under each tier's two-device
    mesh: ``kernels.per_device`` splits the batch rows over the slice's
    data axis, and the streams stay bit-identical to the mesh-less
    colocated run of the same kernels."""
    out = run_ndev("""
import dataclasses
from repro.configs import get_config
from repro.models import model_zoo
from repro.dist import sharding as sh
from repro.kernels import per_device
from repro.kernels.paged_attention import ops as pa
from repro.serve import scheduler as sched_lib
from repro.serve import disagg as disagg_lib

# the wrapper alone: rows split over a 2-device mesh == unsplit
mesh = sh.slice_mesh(jax.devices()[:2])
key = jax.random.PRNGKey(0)
q = jax.random.normal(key, (4, 1, 4, 16), jnp.float32)
kp = jax.random.normal(jax.random.fold_in(key, 1), (12, 2, 4, 16))
vp = jax.random.normal(jax.random.fold_in(key, 2), (12, 2, 4, 16))
table = jnp.arange(12, dtype=jnp.int32).reshape(4, 3)
cur = jnp.array([1, 5, 9, 12], jnp.int32)
ref = pa.paged_attention(q, kp, vp, table, cur)
on = lambda a: jax.device_put(a, jax.NamedSharding(
    mesh, jax.P("data")))      # rows, and the pools' blocks, as served
with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
    got = jax.jit(pa.paged_attention)(*map(on, (q, kp, vp, table, cur)))
    text = jax.jit(lambda *a: per_device(lambda x: x * 2, rows=(0,))(*a)
                   ).lower(q).as_text()
np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
assert "shard_map" in text or "sdy.manual_computation" in text, text

cfg = dataclasses.replace(get_config("smollm-135m", smoke=True),
                          attn_impl="pallas")
KEY = jax.random.PRNGKey(3)
params = model_zoo.init_params(cfg, KEY)
pf_dev, de_dev = sh.carve_slices(2)
pf_mesh, de_mesh = sh.slice_mesh(pf_dev), sh.slice_mesh(de_dev)
prompts = [jax.random.randint(jax.random.fold_in(KEY, b), (1, L), 2,
                              cfg.vocab)
           for b, L in enumerate((3, 9, 16, 12))]
co = sched_lib.DecodeScheduler(
    params, cfg, n_slots=2, prompt_len=16, max_new_cap=5, eos_id=1,
    kv="paged", kv_block=4, prefill="chunked", chunk_tokens=8)
for b, p in enumerate(prompts):
    co.submit(p, max_new=5, request_id=b)
ref = {f.request_id: np.asarray(f.tokens)
       for f in co.run_until_drained()}
d = disagg_lib.DisaggScheduler(
    params, cfg, n_prefill_slots=2, n_decode_slots=2, prompt_len=16,
    max_new_cap=5, eos_id=1, prefill_mesh=pf_mesh, decode_mesh=de_mesh,
    kv_block=4, chunk_tokens=8)
for b, p in enumerate(prompts):
    d.submit(p, max_new=5, request_id=b)
got = {f.request_id: np.asarray(f.tokens)
       for f in d.run_until_drained()}
assert got.keys() == ref.keys()
for rid in ref:
    np.testing.assert_array_equal(got[rid], ref[rid])
assert d.attn_impl == "pallas-paged:interpret", d.attn_impl
print("DISAGG_PALLAS_OK")
""", n_devices=4)
    assert "DISAGG_PALLAS_OK" in out
