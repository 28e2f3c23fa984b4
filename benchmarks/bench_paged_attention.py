"""Paged-attention decode: gather-based vs gather-free step cost.

PR 3's ``PagedKVCache`` pays a transient dense-layout reconstruction
(``PagedView.gather`` → ``(rows, max_len, KV, hd)`` K and V, per layer,
per decode step) to stay XLA-portable and bit-identical. The Pallas
paged-attention kernel (``repro.kernels.paged_attention``) reads K/V
through the block table instead, so the dense layout is NEVER
materialized on the decode hot path.

Protocol: one paged slot pool at a 7:1 short/long ``cur_len`` mix,
jitted ``engine.decode_step`` through both paths across block sizes:

- timing: median step wall time for each path (off TPU the kernel runs
  in INTERPRET mode — a correctness fallback whose timings are not TPU
  numbers; the printed name says which ran);
- memory: a static guarantee, not a sample — the jaxpr of the
  gather-free step is walked recursively and asserted to contain NO
  dense-layout K/V intermediate (any ``(rows, >=max_len)``-shaped K/V
  value), while the gather step must contain them (detector sanity).
  Per-step dense-intermediate bytes are derived from the shapes found.

``--smoke`` runs the static check + one step of each path and asserts
the acceptance bound (kernel: 0 dense intermediates).

CSV rows: name,us_per_call,derived.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core
import numpy as np

from repro.configs import get_config
from repro.models import model_zoo
from repro.serve import engine

ROWS = 8
MAX_LEN = 128
SHORT, LONG = 16, 112        # 7:1 mix like bench_paged_kv
BLOCKS = (8, 16, 32)


# --------------- static jaxpr inspection ------------------------------------

def _collect_shapes(jaxpr, out):
    """All intermediate avals in a jaxpr, recursing into sub-jaxprs
    (scan/while bodies, pallas kernels, custom-jvp calls, ...)."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                out.append((tuple(aval.shape), getattr(aval, "dtype", None)))
        for val in eqn.params.values():
            for leaf in (val if isinstance(val, (tuple, list)) else (val,)):
                if isinstance(leaf, jex_core.ClosedJaxpr):
                    _collect_shapes(leaf.jaxpr, out)
                elif isinstance(leaf, jex_core.Jaxpr):
                    _collect_shapes(leaf, out)


def dense_kv_intermediates(fn, args, *, rows, max_len, kv, hd):
    """(count, bytes) of dense-layout K/V intermediates in ``fn``'s
    jaxpr: any value shaped ``(rows, T>=max_len, kv, hd)`` (the gather
    output / its slice), or a 5-d block gather covering >= max_len
    positions: ``(rows, bpr, kv, block, hd)`` as read from the
    head-major pool, ``(rows, bpr, block, kv, hd)`` once swapped for
    the reshape."""
    shapes = []
    _collect_shapes(jax.make_jaxpr(fn)(*args).jaxpr, shapes)
    n, nbytes = 0, 0
    for s, dt in shapes:
        hit = (len(s) == 4 and s[0] == rows and s[2] == kv and s[3] == hd
               and s[1] >= max_len) or \
              (len(s) == 5 and s[0] == rows and s[3] == kv and s[4] == hd
               and s[1] * s[2] >= max_len) or \
              (len(s) == 5 and s[0] == rows and s[2] == kv and s[4] == hd
               and s[1] * s[3] >= max_len)
        if hit:
            n += 1
            nbytes += int(np.prod(s)) * jnp.dtype(dt).itemsize
    return n, nbytes


# --------------- harness ----------------------------------------------------

def _setup(arch: str, block: int, attn_impl: str):
    cfg = dataclasses.replace(get_config(arch, smoke=True),
                              attn_impl=attn_impl)
    params = model_zoo.init_params(cfg, jax.random.PRNGKey(0))
    cache = engine.make_cache(cfg, ROWS, MAX_LEN, kv_impl="paged",
                              kv_block=block)
    key = engine.kv_key(cfg)
    cache[key] = cache[key].alloc(jnp.arange(ROWS, dtype=jnp.int32),
                                  jnp.full((ROWS,), MAX_LEN, jnp.int32))
    # 7 short : 1 long per-row depths (slot pool at mixed depths)
    cur = jnp.asarray([LONG if i % 8 == 7 else SHORT
                       for i in range(ROWS)], jnp.int32)
    tok = jnp.zeros((ROWS, 1), jnp.int32)
    step = jax.jit(lambda p, t, c, cl: engine.decode_step(p, cfg, t, c, cl))
    return cfg, params, cache, tok, cur, step


def _time(step, params, tok, cache, cur, iters: int = 20) -> float:
    out = step(params, tok, cache, cur)
    jax.block_until_ready(out[0])      # compile outside the timed window
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = step(params, tok, cache, cur)
        jax.block_until_ready(out[0])
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def measure(arch: str = "llama3.2-1b", block: int = 16, iters: int = 20):
    """One block size, both paths: (times, dense-intermediate stats)."""
    res = {}
    for impl in ("xla", "pallas"):
        cfg, params, cache, tok, cur, step = _setup(arch, block, impl)
        n, nbytes = dense_kv_intermediates(
            lambda p, t, c, cl: engine.decode_step(p, cfg, t, c, cl),
            (params, tok, cache, cur), rows=ROWS, max_len=MAX_LEN,
            kv=cfg.n_kv_heads, hd=cfg.resolved_head_dim)
        res[impl] = {"t": _time(step, params, tok, cache, cur, iters),
                     "dense_n": n, "dense_bytes": nbytes,
                     "ran": engine.resolved_attn_impl(cfg, "paged")}
    return res


def check_static(arch: str = "smollm-135m", block: int = 8):
    """The acceptance bound, as a pure-trace check (no timing): the
    gather-free step allocates NO dense K/V intermediate; the gather
    step does (detector sanity). Returns the two (count, bytes)."""
    out = {}
    for impl in ("xla", "pallas"):
        cfg, params, cache, tok, cur, _ = _setup(arch, block, impl)
        out[impl] = dense_kv_intermediates(
            lambda p, t, c, cl: engine.decode_step(p, cfg, t, c, cl),
            (params, tok, cache, cur), rows=ROWS, max_len=MAX_LEN,
            kv=cfg.n_kv_heads, hd=cfg.resolved_head_dim)
    assert out["pallas"][0] == 0, \
        f"gather-free path still materializes dense K/V: {out['pallas']}"
    assert out["xla"][0] > 0, \
        "detector found no dense K/V in the gather path (detector broken?)"
    return out


def rows():
    out = []
    static = check_static()
    for block in BLOCKS:
        r = measure(block=block)
        x, p = r["xla"], r["pallas"]
        out.append((f"PagedAttn/gather-b{block}", x["t"] * 1e6,
                    f"{x['ran']} dense-KV intermediates/step="
                    f"{x['dense_n']} ({x['dense_bytes'] >> 10}KiB)"))
        out.append((f"PagedAttn/kernel-b{block}", p["t"] * 1e6,
                    f"{p['ran']} dense-KV intermediates/step=0 "
                    f"({x['t'] / p['t']:.2f}x vs gather; interpret-mode "
                    f"timings are NOT TPU numbers)"))
    out.append(("PagedAttn/static-check", 0.0,
                f"gather allocates {static['xla'][0]} dense K/V "
                f"intermediates ({static['xla'][1] >> 10}KiB/step); "
                f"kernel allocates 0"))
    return out


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI run: static no-dense-intermediate assert + "
                         "one step of each path across two block sizes")
    args = ap.parse_args()
    if args.smoke:
        for block in (4, 8):
            static = check_static(block=block)
            print(f"block={block}: gather dense-KV intermediates="
                  f"{static['xla'][0]} ({static['xla'][1] >> 10}KiB), "
                  f"kernel=0")
        # both paths actually execute (one step each, token parity)
        outs = {}
        for impl in ("xla", "pallas"):
            cfg, params, cache, tok, cur, step = _setup("smollm-135m", 8,
                                                        impl)
            logits, _ = step(params, tok, cache, cur)
            outs[impl] = np.asarray(jnp.argmax(logits[:, 0], -1))
        np.testing.assert_array_equal(outs["xla"], outs["pallas"])
        print("PAGED_ATTENTION_SMOKE_OK")
        return
    for name, us, derived in rows():
        print(f"{name},{us:.2f},{derived}")


if __name__ == "__main__":
    main()
