"""Disaggregated vs colocated serving: running-slot p99 inter-token
latency under long-prompt interference, at equal device count.

Colocated chunked prefill (``bench_chunked_prefill``) bounds the
admission stall at one chunk per iteration — but never removes it:
while a 512-token prompt streams in, EVERY decode iteration also
carries a chunk's worth of prefill FLOPs, so running slots' inter-token
gaps inflate by the chunk cost for the whole admission. Disaggregation
(``repro.serve.disagg``, DESIGN.md §8.7) moves that work onto a
disjoint prefill slice: the decode slice's iterations are pure decode,
the prefill slice chews chunks concurrently, and finished KV blocks
ship slice-to-slice asynchronously — so a long prompt never appears in
a running slot's gap at all.

Protocol (closed loop, identical workload): ``N_REQ`` requests at a
7:1 short/long PROMPT mix (``LONG_PROMPT = 512``), staggered budgets,
submitted up front. Both servers see the same total device fleet —
the colocated scheduler gets ONE mesh over all devices, the
disaggregated scheduler carves the same devices into half prefill /
half decode — so the comparison isolates placement, not capacity.
Each scheduler round is a host-visible delivery boundary; a round's
wall is spread over its decode iterations (the delivery clock), and a
running slot that kept its residency records one gap per token.
Rounds that deliver nothing (pure prefill/ship work) accumulate into
the NEXT delivery's first gap, so disaggregation pays honestly for any
round it spends not decoding. p99 is over those gaps.

The PR-4 static guarantee extends across the wire: the export ->
device_put -> import shipping path is walked per layer and asserted to
materialize ZERO dense ``(rows, >= max_len, KV, hd)`` K/V
intermediates on either slice (a deliberately densified wire buffer IS
flagged — detector sanity). The KV a prompt prefilled on one slice
reaches the other slice's kernel block-granular, end to end.

``--smoke`` asserts the acceptance bound (p99 ratio >= 2.0x at >= 0.4x
throughput) and records ``BENCH_disagg.json`` at the repo root (CI
uploads it, so the perf trajectory is recorded per commit).

Run standalone, the script forces 8 virtual host devices BEFORE jax
imports so the submesh split is real even on a laptop; under
``benchmarks/run.py`` it inherits whatever fleet the process already
locked (CI's 8-device job sets the flag globally).

CSV rows: name,us_per_call,derived.
"""

from __future__ import annotations

import json
import os
import sys
import time

if "jax" not in sys.modules and "device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    # forced host devices are CPU devices: pin the backend to them
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

try:
    from .bench_paged_attention import dense_kv_intermediates
except ImportError:                      # run as a script
    from bench_paged_attention import dense_kv_intermediates

from repro.configs import get_config
from repro.dist import sharding as sh
from repro.models import model_zoo
from repro.serve import disagg as disagg_lib
from repro.serve import engine
from repro.serve import kv_cache as kvc
from repro.serve import scheduler as sched_lib

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SLOTS = 4                                # decode-tier / colocated slots
PREFILL_SLOTS = 2
SHORT_PROMPT, LONG_PROMPT = 8, 512       # 7:1 mix; LONG is the interference
BUDGETS = (6, 10, 14, 18, 22)
MAX_NEW_CAP = max(BUDGETS)
CHUNK = 16
BLOCK = 8
EOS = -1          # budget-only retirement keeps both modes' work equal
SEGMENT = 8


# --------------- static jaxpr check (shipping path) --------------------------

def check_static_ship(arch: str = "smollm-135m", block: int = BLOCK):
    """The export -> import block shipment allocates NO dense-layout
    K/V intermediate (and a densified wire buffer trips the detector).
    Returns {'ship': (count, bytes), 'densified': (count, bytes)}."""
    cfg = get_config(arch, smoke=True)
    rows, max_len = SLOTS, 64
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

    def densified(src):
        return (src.export_rows(r, n_cols)[0][0].swapaxes(2, 3)
                .reshape(rows, n_cols * block, kvh, hd))

    out = {
        "ship": dense_kv_intermediates(
            ship, (cache, cache), rows=rows, max_len=max_len, kv=kvh,
            hd=hd),
        "densified": dense_kv_intermediates(
            densified, (cache,), rows=rows, max_len=max_len, kv=kvh,
            hd=hd),
    }
    assert out["ship"][0] == 0, \
        f"KV shipment materializes dense K/V: {out['ship']}"
    assert out["densified"][0] > 0, \
        "detector found no dense K/V in a densified wire (broken?)"
    return out


# --------------- latency harness --------------------------------------------

def _workload(n_req: int, rng):
    """7 short : 1 long prompts, staggered budgets, submitted up front."""
    reqs = []
    for i in range(n_req):
        plen = LONG_PROMPT if i % 8 == 3 else SHORT_PROMPT
        reqs.append((rng.integers(2, 512, (1, plen)).astype(np.int32),
                     BUDGETS[i % len(BUDGETS)]))
    return reqs


def _drive(sched, reqs, pool_of, steps_of):
    """Closed loop over ``sched.step(expect_arrivals=True)``; returns
    gap samples, wall, tokens. ``pool_of``/``steps_of`` read the
    DELIVERY tier (the colocated pool, or the decode tier of the
    disaggregated pair) — its iterations are the delivery clock.

    Round accounting: a round's wall spreads evenly over its decode
    iterations; a round that delivered no decode iteration (prefill /
    ship / splice only) carries its whole wall into the next
    delivery's first gap. A slot's first-ever token is TTFT, not an
    inter-token gap (excluded)."""
    sched.warmup()
    rng = np.random.default_rng(1)
    for i, plen in enumerate((SHORT_PROMPT, LONG_PROMPT)):
        sched.submit(rng.integers(2, 512, (1, plen)).astype(np.int32),
                     max_new=1, request_id=10_000 + i)
        sched.run_until_drained()     # warm both prompt buckets + wire
    tokens0 = sched.tokens_emitted
    for i, (prompt, max_new) in enumerate(reqs):
        sched.submit(prompt, max_new=max_new, request_id=i)
    n = pool_of().request_id.shape[0]
    prev_rid = np.full(n, -2, np.int64)
    prev_n = np.zeros(n, np.int64)
    gaps = []
    stall = 0.0
    t0 = time.perf_counter()
    steps_prev = steps_of()
    while sched.pending:
        ts = time.perf_counter()
        sched.step(expect_arrivals=True)
        W = time.perf_counter() - ts
        K = steps_of() - steps_prev
        steps_prev = steps_of()
        if K == 0:
            stall += W          # a non-delivering round: someone waits
            continue
        per_iter = W / K
        pool = pool_of()
        n_em = np.asarray(pool.n_emitted)
        rids = np.asarray(pool.request_id)
        for s in range(n):
            rid, ne = int(rids[s]), int(n_em[s])
            if rid != prev_rid[s]:
                prev_rid[s] = rid
                prev_n[s] = ne
                if ne > 1:               # first delivery: internal gaps
                    gaps.extend([per_iter] * (ne - 1))
                continue
            d = ne - prev_n[s]
            if d <= 0:
                continue
            if prev_n[s] > 0:            # had tokens: stalled rounds land
                gaps.append(stall + per_iter)
                gaps.extend([per_iter] * (d - 1))
            elif d > 1:                  # first delivery mid-stream
                gaps.extend([per_iter] * (d - 1))
            prev_n[s] = ne
        stall = 0.0
    wall = time.perf_counter() - t0
    return {"gaps": gaps, "wall": wall,
            "tokens": sched.tokens_emitted - tokens0}


def _meshes():
    """(full, prefill, decode) meshes over the visible fleet — all
    None on a single device (both tiers share it; the ship/splice path
    still runs)."""
    n = jax.device_count()
    if n < 2:
        return None, None, None
    pf, de = sh.carve_slices(n // 2)
    return (sh.slice_mesh(jax.devices()), sh.slice_mesh(pf),
            sh.slice_mesh(de))


def run(n_req: int = 32, arch: str = "smollm-135m"):
    cfg = get_config(arch, smoke=True)
    params = model_zoo.init_params(cfg, jax.random.PRNGKey(0))
    full_mesh, pf_mesh, de_mesh = _meshes()
    rng = np.random.default_rng(0)
    reqs = _workload(n_req, rng)
    res = {}

    co = sched_lib.DecodeScheduler(
        params, cfg, n_slots=SLOTS, prompt_len=LONG_PROMPT,
        max_new_cap=MAX_NEW_CAP, eos_id=EOS, kv="paged",
        kv_block=BLOCK, prefill="chunked", chunk_tokens=CHUNK,
        rules=disagg_lib._slice_rules(cfg, full_mesh), mesh=full_mesh)
    r = _drive(co, reqs, lambda: co.pool, lambda: co.total_steps)
    gaps = np.asarray(r["gaps"])
    res["colocated"] = {
        "tok_s": r["tokens"] / r["wall"],
        "p50_ms": float(np.percentile(gaps, 50) * 1e3),
        "p99_ms": float(np.percentile(gaps, 99) * 1e3),
        "wall_s": r["wall"], "tokens": int(r["tokens"]),
        "devices": jax.device_count(),
        "transfer_impl": co.transfer_impl,
    }

    d = disagg_lib.DisaggScheduler(
        params, cfg, n_prefill_slots=PREFILL_SLOTS,
        n_decode_slots=SLOTS, prompt_len=LONG_PROMPT,
        max_new_cap=MAX_NEW_CAP, eos_id=EOS, prefill_mesh=pf_mesh,
        decode_mesh=de_mesh, kv_block=BLOCK, chunk_tokens=CHUNK,
        segment_steps=SEGMENT)
    r = _drive(d, reqs, lambda: d.decode.pool, lambda: d.total_steps)
    gaps = np.asarray(r["gaps"])
    res["disagg"] = {
        "tok_s": r["tokens"] / r["wall"],
        "p50_ms": float(np.percentile(gaps, 50) * 1e3),
        "p99_ms": float(np.percentile(gaps, 99) * 1e3),
        "wall_s": r["wall"], "tokens": int(r["tokens"]),
        "prefill_devices": (len(pf_mesh.devices.flat) if pf_mesh
                            else 1),
        "decode_devices": (len(de_mesh.devices.flat) if de_mesh
                           else 1),
        "transfers": d.transfers,
        "transfer_bytes": d.transfer_bytes,
        "transfer_impl": d.transfer_impl,
        "prefill_steps": d.prefill_steps,
    }
    res["p99_ratio"] = (res["colocated"]["p99_ms"]
                        / res["disagg"]["p99_ms"])
    res["tok_s_ratio"] = (res["disagg"]["tok_s"]
                          / res["colocated"]["tok_s"])
    return res


def write_json(res, static, path=None):
    """Record the trajectory point: BENCH_disagg.json at the repo root
    (uploaded as a CI artifact)."""
    path = path or os.path.join(REPO_ROOT, "BENCH_disagg.json")
    doc = {
        "bench": "disagg",
        "workload": {"slots": SLOTS, "prefill_slots": PREFILL_SLOTS,
                     "short_prompt": SHORT_PROMPT,
                     "long_prompt": LONG_PROMPT, "mix": "7:1",
                     "budgets": list(BUDGETS), "chunk_tokens": CHUNK,
                     "kv_block": BLOCK, "segment_steps": SEGMENT},
        "colocated": res["colocated"],
        "disagg": res["disagg"],
        "p99_inter_token_ratio": res["p99_ratio"],
        "tok_s_ratio": res["tok_s_ratio"],
        "static_dense_kv_intermediates": {
            "ship_path": static["ship"][0],
            "densified_wire": static["densified"][0]},
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
    return path


_LAST = {}   # rows() stashes its measurements so --json doesn't re-run


def rows():
    static = check_static_ship()
    res = run()
    _LAST["static"], _LAST["res"] = static, res
    c, g = res["colocated"], res["disagg"]
    out = [
        ("Disagg/colocated", c["p99_ms"] * 1e3,
         f"{c['devices']}dev tok/s={c['tok_s']:.1f} "
         f"p50={c['p50_ms']:.0f}ms p99={c['p99_ms']:.0f}ms"),
        ("Disagg/disagg", g["p99_ms"] * 1e3,
         f"{g['prefill_devices']}+{g['decode_devices']}dev "
         f"({g['transfer_impl']}) tok/s={g['tok_s']:.1f} "
         f"p50={g['p50_ms']:.0f}ms p99={g['p99_ms']:.0f}ms "
         f"ship={g['transfers']}x{g['transfer_bytes'] // max(g['transfers'], 1)}B"),
        ("Disagg/p99-ratio", 0.0,
         f"{res['p99_ratio']:.2f}x lower p99 inter-token latency at "
         f"{res['tok_s_ratio']:.2f}x throughput (7:1 short/long, "
         f"long={LONG_PROMPT})"),
        ("Disagg/static-check", 0.0,
         f"ship path allocates 0 dense K/V intermediates "
         f"(densified wire: {static['densified'][0]})"),
    ]
    write_json(res, static)
    return out


def json_summary():
    """Structured record for benchmarks/run.py --json (reuses the
    measurements the preceding rows() call already took)."""
    if "res" in _LAST:
        static, res = _LAST["static"], _LAST["res"]
    else:
        static, res = check_static_ship(), run()
    return {"colocated": res["colocated"], "disagg": res["disagg"],
            "p99_inter_token_ratio": res["p99_ratio"],
            "tok_s_ratio": res["tok_s_ratio"],
            "static_dense_kv_intermediates": {
                "ship_path": static["ship"][0],
                "densified_wire": static["densified"][0]}}


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI run: static no-dense-intermediate assert "
                         "+ reduced workload, asserts p99 ratio >= "
                         "2.0x at >= 0.4x throughput; writes "
                         "BENCH_disagg.json")
    args = ap.parse_args()
    static = check_static_ship()
    print(f"static: ship-path dense-KV intermediates="
          f"{static['ship'][0]}, densified wire="
          f"{static['densified'][0]}")
    # CPU CI wall clocks are noisy; the p99 bound holds with wide
    # margin in practice but the smoke still gets one retry. The
    # throughput floor is loose — the decode slice is half the fleet,
    # and the ship/splice overhead rides the measured walls (recorded
    # in the JSON either way).
    attempts = 2 if args.smoke else 1
    for attempt in range(attempts):
        res = run(n_req=16 if args.smoke else 32)
        path = write_json(res, static)
        c, g = res["colocated"], res["disagg"]
        print(f"colocated ({c['devices']}dev, {c['transfer_impl']}): "
              f"{c['tok_s']:.1f} tok/s p50 {c['p50_ms']:.0f}ms "
              f"p99 {c['p99_ms']:.0f}ms")
        print(f"disagg ({g['prefill_devices']}+{g['decode_devices']}"
              f"dev, {g['transfer_impl']}): {g['tok_s']:.1f} tok/s "
              f"p50 {g['p50_ms']:.0f}ms p99 {g['p99_ms']:.0f}ms | "
              f"{g['transfers']} shipments, "
              f"{g['transfer_bytes'] / 1e6:.2f} MB")
        print(f"p99 inter-token ratio {res['p99_ratio']:.2f}x at "
              f"{res['tok_s_ratio']:.2f}x throughput -> {path}")
        if res["p99_ratio"] >= 2.0 and res["tok_s_ratio"] >= 0.4:
            break
    if args.smoke:
        assert res["p99_ratio"] >= 2.0, \
            f"p99 ratio {res['p99_ratio']:.2f} < 2.0"
        assert res["tok_s_ratio"] >= 0.4, \
            f"throughput ratio {res['tok_s_ratio']:.2f} < 0.4"
        print("DISAGG_SMOKE_OK")


if __name__ == "__main__":
    main()
