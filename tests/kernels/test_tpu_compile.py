"""Compile-only checks of the serving kernels for a described TPU v5e.

Interpret mode on the CPU accepts any block shape, so the parity tests
in this directory cannot see what the TPU compiler refuses (for
example a block whose last two dims are neither multiples of (8, 128)
nor whole array dims). These tests compile each main-path kernel, at
published widths, for a v5e chip that is described and not attached,
and check that the compiled program holds the Mosaic kernel
(``tpu_custom_call``). Nothing runs.

The topology is described inside a fixture: only the worker that runs
this file loads the TPU compiler library, and every worker collects the
same tests.
"""

import dataclasses
import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

# (H, KV, hd): olmo-1b (MHA, the smoke model on the chip), qwen2-7b
# (GQA 7:1), smollm-135m (head_dim 64)
WIDTHS = {"olmo-1b": (16, 16, 128), "gqa-kv4": (28, 4, 128),
          "hd64": (9, 3, 64)}
BLOCK = 16              # the launcher's --kv-block default (bf16 tile)
B, BPR, N_BLOCKS = 4, 8, 40


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # any failure: no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture
def tpu_ops(monkeypatch):
    """The kernels' ops modules, steered to their compiled (not
    interpret-mode) form: ``on_tpu()`` reads the CPU backend here."""
    from repro.kernels.flash_attention import ops as fa
    from repro.kernels.flash_prefill import ops as fp
    from repro.kernels.grouped_matmul import ops as gm
    from repro.kernels.paged_attention import ops as pa
    for mod in (fa, fp, pa, gm):
        monkeypatch.setattr(mod, "on_tpu", lambda: True)
    return fa, fp, pa


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _paged_args(sharding, H, KV, hd, C):
    s = functools.partial(jax.ShapeDtypeStruct, sharding=sharding)
    pool = s((N_BLOCKS, KV, BLOCK, hd), jnp.bfloat16)
    return (s((B, C, H, hd), jnp.bfloat16), pool, pool,
            s((B, BPR), jnp.int32), s((B,), jnp.int32))


# decode kernel cases: a width at the small table above, and the two
# benchmark cells' real shapes (rows, blocks per row, pool blocks), whose
# chunks of pages set the kernel's VMEM buffers
PAGED = {w: (w, B, BPR, N_BLOCKS) for w in WIDTHS}
PAGED.update({"olmo-1b.chat": ("olmo-1b", 32, 128, 800),
              "qwen2-7b-pp4.decode_batch": ("gqa-kv4", 128, 97, 2800)})


@pytest.mark.parametrize("width", sorted(PAGED))
def test_paged_attention_compiles(width, one_chip, no_compile_cache,
                                  tpu_ops):
    _, _, pa = tpu_ops
    name, b, bpr, n_blocks = PAGED[width]
    H, KV, hd = WIDTHS[name]
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    pool = s((n_blocks, KV, BLOCK, hd), jnp.bfloat16)
    text = _compiled_text(pa.paged_attention, s((b, 1, H, hd), jnp.bfloat16),
                          pool, pool, s((b, bpr), jnp.int32),
                          s((b,), jnp.int32))
    assert "tpu_custom_call" in text


def test_paged_attention_custom_call_name(one_chip, no_compile_cache,
                                          tpu_ops):
    """The benchmark's trace reduction finds the decode kernel's time by
    the prefix ``%paged_attention`` of its custom call, which takes the
    name of the function wrapping the ``pallas_call``: it must hold
    inside a caller's program too, one kernel per layer."""
    _, _, pa = tpu_ops
    H, KV, hd = WIDTHS["olmo-1b"]
    q, k, v, table, cur = _paged_args(one_chip, H, KV, hd, 1)

    def two_layers(q, k, v, table, cur):
        for _ in range(2):
            q = pa.paged_attention(q, k, v, table, cur)
        return q

    calls = [line.split("=")[0].split()[-1]
             for line in _compiled_text(two_layers, q, k, v, table,
                                        cur).splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2
    assert all(c.startswith("%paged_attention") for c in calls), calls


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_flash_prefill_compiles(width, one_chip, no_compile_cache, tpu_ops):
    _, fp, _ = tpu_ops
    H, KV, hd = WIDTHS[width]
    # a 128-token prefill chunk, the smoke run's --chunk-tokens
    text = _compiled_text(fp.flash_prefill,
                          *_paged_args(one_chip, H, KV, hd, 128))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_flash_verify_compiles(width, one_chip, no_compile_cache, tpu_ops):
    _, fp, _ = tpu_ops
    H, KV, hd = WIDTHS[width]
    # a k=4 speculative window: W * G rows padded up to the sublane
    text = _compiled_text(fp.flash_verify,
                          *_paged_args(one_chip, H, KV, hd, 5))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_flash_attention_compiles(width, one_chip, no_compile_cache,
                                  tpu_ops):
    fa, _, _ = tpu_ops
    H, KV, hd = WIDTHS[width]
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    S = 256
    text = _compiled_text(fa.flash_attention, s((2, S, H, hd), jnp.bfloat16),
                          s((2, S, KV, hd), jnp.bfloat16),
                          s((2, S, KV, hd), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_flash_attention_compiles_on_slice(topo, no_compile_cache,
                                           tpu_ops):
    """Full-sequence attention with its batch rows sharded over a
    two-chip slice: the kernel must wrap itself for the context mesh."""
    from repro.dist import sharding as sh
    fa, _, _ = tpu_ops
    H, KV, hd = WIDTHS["olmo-1b"]
    mesh = sh.slice_mesh(topo.devices[:2])
    s = functools.partial(jax.ShapeDtypeStruct,
                          sharding=NamedSharding(mesh, PartitionSpec("data")))
    S = 256
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        text = _compiled_text(fa.flash_attention,
                              s((2, S, H, hd), jnp.bfloat16),
                              s((2, S, KV, hd), jnp.bfloat16),
                              s((2, S, KV, hd), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_sliced_pool_step_compiles(topo, no_compile_cache, tpu_ops):
    """A scheduler step whose pool is sharded over a two-chip slice
    (one tier of disaggregated serving): the TPU compiler cannot
    partition a Mosaic kernel, so the kernels must wrap themselves in a
    ``shard_map`` over the context mesh the scheduler sets."""
    from repro.configs import get_config
    from repro.dist import sharding as sh
    from repro.models import model_zoo
    from repro.serve import disagg
    from repro.serve import scheduler as sched_lib

    cfg = dataclasses.replace(get_config("olmo-1b", smoke=True),
                              attn_impl="pallas")
    mesh = sh.slice_mesh(topo.devices[:2])
    rules = disagg._slice_rules(cfg, mesh)
    sched = sched_lib.DecodeScheduler(
        jax.eval_shape(lambda: model_zoo.init_params(
            cfg, jax.random.PRNGKey(0))), cfg,
        n_slots=2, prompt_len=32, max_new_cap=8, kv="paged",
        kv_block=BLOCK, prefill="chunked", chunk_tokens=16)
    shd = sched_lib.pool_shardings(
        cfg, 2, sched.max_len, 8, rules, mesh, kv="paged",
        kv_block=BLOCK, kv_blocks=sched.kv_blocks, prompt_len=32)
    pool = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        sched.pool, shd)
    rep = NamedSharding(mesh, PartitionSpec())
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        sched.params)
    scalar = jax.ShapeDtypeStruct((), np.int32, sharding=rep)
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        text = jax.jit(sched._build_step()).lower(
            params, None, pool, scalar, scalar).compile().as_text()
    assert text.count("tpu_custom_call") >= 2   # decode + chunk kernels


# moonlight-16b-a3b-ep8pp2.doc_batch: 16 heads of 640 lanes (the 576
# values of a latent row, padded to whole lane tiles) over one shared
# latent head, 128 rows, 193 blocks per row, the mix's 7168 blocks
LATENT = dict(H=16, lanes=640, rows=128, bpr=193, n_blocks=7168, chunk=64)


def _custom_calls(text):
    return [line.split("=")[0].split()[-1] for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


@pytest.mark.parametrize("mode", ["decode", "chunk"])
def test_latent_pool_kernels_compile(mode, one_chip, no_compile_cache,
                                     tpu_ops):
    """Latent attention's cache reads at the cell's shapes: the decode
    and chunk-prefill kernels with the latent pool as their one K head
    and no V pool, under the names the trace reduction reads."""
    _, fp, pa = tpu_ops
    a = LATENT
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    pool = s((a["n_blocks"], 1, BLOCK, a["lanes"]), jnp.bfloat16)
    table, rows = s((a["rows"], a["bpr"]), jnp.int32), s((a["rows"],),
                                                          jnp.int32)
    if mode == "decode":
        fn = functools.partial(pa.paged_attention, scale=192 ** -0.5)
        q, name = s((a["rows"], 1, a["H"], a["lanes"]), jnp.bfloat16), \
            "%paged_attention"
    else:
        fn = functools.partial(fp.flash_prefill, scale=192 ** -0.5)
        q, name = s((a["rows"], a["chunk"], a["H"], a["lanes"]),
                    jnp.bfloat16), "%flash_prefill"
    calls = _custom_calls(_compiled_text(
        lambda q, k, t, r: fn(q, k, None, t, r), q, pool, table, rows))
    assert len(calls) == 1 and calls[0].startswith(name), calls


# (entries, in, out): a decode step's 128 x 6 entries and a chunk
# iteration's 128 x 64 x 6, through gate/up (2048 -> 1408) and down
GMM = {"decode_up": (768, 2048, 1408), "chunk_up": (49152, 2048, 1408),
       "chunk_down": (49152, 1408, 2048)}


@pytest.mark.parametrize("case", sorted(GMM))
def test_grouped_matmul_compiles(case, one_chip, no_compile_cache, tpu_ops):
    """The megablox grouped matmul over the 8 experts held here, at the
    cell's entry counts, as the ``gmm`` custom call."""
    from repro.kernels.grouped_matmul import ops as gm
    M, K, N = GMM[case]
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    calls = _custom_calls(_compiled_text(
        gm.expert_gmm, s((M, K), jnp.bfloat16), s((8, K, N), jnp.bfloat16),
        s((8,), jnp.int32)))
    assert calls and all(c.startswith("%gmm") for c in calls), calls
