#!/usr/bin/env python3
"""Smoke test of the serving main path on TPU, in one process.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # disaggregated 2+2 vs colocated

One chip runs two phases on olmo-1b at its published widths (16 layers,
d_model 2048, 16 heads of 128, vocab 50304), with random weights drawn
from ``--seed``:

1. serve: 8 requests of 512-token prompts (32 or 64 new tokens each)
   over 4 slots, through ``launch.serve.run_continuous`` — the
   launcher's own ``DecodeScheduler`` with paged KV (16-token blocks),
   chunked prefill (128-token chunks) and the Pallas kernels. Fails
   unless both kernel paths report ``:compiled`` and every request
   returns all of its tokens.
2. logits: the same two-chunk prefill and four decode steps, fed
   identical tokens, through the kernels and through the XLA gather
   path (``attn_impl="xla"``); the logits must be finite and agree
   within ``LOGITS_RTOL``.

``--chips 4`` runs only the path that exists across chips: the same
requests through a 2+2 ``DisaggScheduler`` (prefill and decode tiers on
disjoint halves of the fleet, KV blocks shipped between them) and
through colocated chunked serving, whose greedy streams must be
token-identical.

Without a TPU it exits non-zero and prints no result. Its last line on
success is ``{"ok": true, "device": {...}}``.
"""

import argparse
import dataclasses
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

ARCH = "olmo-1b"
SERVE_ARGS = ["--arch", ARCH, "--kv", "paged", "--kv-block", "16",
              "--prefill", "chunked", "--chunk-tokens", "128",
              "--attn-impl", "pallas", "--slots", "4", "--requests", "8",
              "--prompt-len", "512", "--max-new-short", "32",
              "--max-new-long", "64", "--rate", "1000",
              # no token ends a request early: each returns max_new
              "--eos-id", "-1"]
# Kernel vs gather logits, as ||a - b|| / ||b|| per (row, position).
# Both paths keep K/V, activations and the residual stream in bf16
# (8 significant bits) and differ in how the softmax accumulates
# (online fp32 in the kernels, bf16 P in the gather prefill), so they
# round differently in each of 16 layers. A 16-layer bf16 forward of
# this family sits ~2.3e-2 from its own fp32 forward (CPU, d_model
# 512); two bf16 paths can sit that far on different sides, so the
# bound is about twice that. A wrong block or head read gives O(1):
# logits of two unrelated rows differ by ~1.4 on this scale.
LOGITS_RTOL = 5e-2


def fail(msg: str) -> None:
    print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def tpu_devices(n_chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX found {devs[0].platform} devices; this smoke "
             f"test never falls back to another backend")
    if len(devs) < n_chips:
        fail(f"--chips {n_chips} needs {n_chips} TPU devices, found "
             f"{len(devs)}")
    return devs


def build(seed: int):
    """Parsed launcher args, model config, params and workload."""
    import jax
    from repro.launch import serve
    from repro.models import model_zoo

    args = serve.build_parser().parse_args(SERVE_ARGS
                                           + ["--seed", str(seed)])
    cfg = serve.model_config(args)
    params = model_zoo.init_params(cfg, jax.random.PRNGKey(seed))
    workload = serve.build_workload(args, np.random.default_rng(seed))
    return serve, args, cfg, params, workload


def check_requests(tag: str, res, workload) -> None:
    toks = res["req_tokens"]
    if sorted(toks) != list(range(len(workload))):
        fail(f"{tag}: finished requests {sorted(toks)} != "
             f"{len(workload)} submitted")
    for rid, (_, max_new) in enumerate(workload):
        if len(toks[rid]) != max_new:
            fail(f"{tag}: request {rid} returned {len(toks[rid])} "
                 f"tokens, wanted {max_new}")


def serve_phase(seed: int):
    serve, args, cfg, params, workload = build(seed)
    say(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads x {cfg.resolved_head_dim}, kv heads "
        f"{cfg.n_kv_heads}, d_ff {cfg.d_ff}, vocab {cfg.vocab}")
    res = serve.run_continuous(args, cfg, params, workload)
    say(f"paths: decode {res['attn_impl']}, prefill {res['prefill_impl']}")
    if res["attn_impl"] != "pallas-paged:compiled":
        fail(f"decode ran {res['attn_impl']}, not pallas-paged:compiled")
    if res["prefill_impl"] != "flash-paged:compiled":
        fail(f"prefill ran {res['prefill_impl']}, not flash-paged:compiled")
    check_requests("serve", res, workload)
    say(f"warmup compile {res['compile_s']:.3f} s")
    say("tokens per request: " + ", ".join(
        f"{rid}:{len(t)}" for rid, t in sorted(res["req_tokens"].items())))
    # counted per request: the scheduler's own counters restart when
    # it drains between arrivals
    returned = sum(len(t) for t in res["req_tokens"].values())
    say(f"serve wall {res['wall_s']:.3f} s, {returned} tokens returned")
    return cfg, params


def logits_phase(cfg, params, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.serve import engine

    n, plen, chunk, steps, block = 2, 256, 128, 4, 16
    max_len = plen + steps
    rng = np.random.default_rng(seed + 1)
    prompts = jnp.asarray(rng.integers(2, cfg.vocab, (n, plen)), jnp.int32)
    dec_tokens = rng.integers(2, cfg.vocab, (steps, n, 1)).astype(np.int32)
    logits = {}
    for impl in ("pallas", "xla"):
        c = dataclasses.replace(cfg, attn_impl=impl)
        cache = engine.make_cache(c, n, max_len, kv_impl="paged",
                                  kv_block=block)
        cache["attn"] = cache["attn"].alloc(
            jnp.arange(n, dtype=jnp.int32),
            jnp.full((n,), max_len, jnp.int32))
        prefill = jax.jit(lambda p, t, ca, off, c=c: engine.prefill_chunk(
            p, c, t, ca, off, chunk=chunk))
        decode = jax.jit(lambda p, t, ca, cl, c=c: engine.decode_step(
            p, c, t, ca, cl))
        out = []
        for off in range(0, plen, chunk):
            lg, cache = prefill(params, prompts, cache,
                                jnp.full((n,), off, jnp.int32))
            out.append(lg)
        for s in range(steps):
            lg, cache = decode(params, dec_tokens[s], cache,
                               jnp.full((n,), plen + s + 1, jnp.int32))
            out.append(lg)
        logits[impl] = np.asarray(
            jnp.concatenate(out, axis=1)[..., :cfg.vocab], np.float32)
    a, b = logits["pallas"], logits["xla"]
    for impl, x in logits.items():
        if not np.isfinite(x).all():
            fail(f"non-finite logits on the {impl} path")
    rel = (np.linalg.norm(a - b, axis=-1)
           / np.maximum(np.linalg.norm(b, axis=-1), 1e-30))
    unrelated = (np.linalg.norm(b[0] - b[1], axis=-1)
                 / np.linalg.norm(b[1], axis=-1))
    say(f"logits kernel vs gather over {n} rows x {plen} prefill + "
        f"{steps} decode positions: max rel L2 {rel.max():.3e} "
        f"(prefill {rel[:, :plen].max():.3e}, decode "
        f"{rel[:, plen:].max():.3e}), max abs {np.abs(a - b).max():.3e}, "
        f"tolerance {LOGITS_RTOL:g}; two unrelated rows differ by "
        f"{unrelated.min():.3f}")
    if rel.max() > LOGITS_RTOL:
        fail(f"kernel logits differ from the gather path by rel L2 "
             f"{rel.max():.3e} > {LOGITS_RTOL:g}")


def disagg_phase(seed: int, devs) -> None:
    serve, args, cfg, params, workload = build(seed)
    col = serve.run_continuous(args, cfg, params, workload)
    check_requests("colocated", col, workload)
    say(f"colocated: decode {col['attn_impl']}, prefill "
        f"{col['prefill_impl']}, compile {col['compile_s']:.3f} s, wall "
        f"{col['wall_s']:.3f} s")
    dargs = argparse.Namespace(**vars(args))
    dargs.disagg, dargs.prefill_devices = True, 2
    dis = serve.run_disagg(dargs, cfg, params, workload)
    check_requests("disagg", dis, workload)
    pf, de = dis["prefill_pool_devices"], dis["decode_pool_devices"]
    say(f"disagg {dis['prefill_devices']}+{dis['decode_devices']}: "
        f"decode {dis['attn_impl']}, prefill {dis['prefill_impl']}, "
        f"compile {dis['compile_s']:.3f} s, wall {dis['wall_s']:.3f} s")
    say(f"prefill pool devices {pf}, decode pool devices {de}")
    say(f"transfer {dis['transfer_impl']}: {dis['transfers']} shipments, "
        f"{dis['transfer_bytes']} bytes")
    all_ids = sorted(d.id for d in devs)
    if set(pf) & set(de) or sorted(pf + de) != all_ids:
        fail(f"tiers not disjoint over all devices {all_ids}: "
             f"prefill {pf}, decode {de}")
    if dis["transfer_impl"] != "device_put:ics":
        fail(f"transfer ran {dis['transfer_impl']}, not device_put:ics")
    if dis["transfers"] < 1:
        fail("no KV shipment between the tiers")
    diverged = {}
    for rid in range(len(workload)):
        a, b = col["req_tokens"][rid], dis["req_tokens"][rid]
        if not np.array_equal(a, b):
            diverged[rid] = int(np.argmax(a != b))
    if diverged:
        fail(f"disaggregated streams differ from colocated in "
             f"{len(diverged)} of {len(workload)} requests; first "
             f"differing token per request: {diverged}")
    say(f"greedy streams token-identical for all {len(workload)} requests")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devs = tpu_devices(args.chips)
    from repro.launch.compile_cache import enable_compile_cache
    say(f"compile cache {enable_compile_cache()}")
    dev = devs[0]
    say(f"platform {dev.platform}, device_kind {dev.device_kind}, "
        f"{len(devs)} device(s)")
    if args.chips == 4:
        disagg_phase(args.seed, devs)
    else:
        cfg, params = serve_phase(args.seed)
        logits_phase(cfg, params, args.seed)
    for d in devs[:args.chips]:
        say(f"device {d.id} peak_bytes_in_use "
            f"{d.memory_stats()['peak_bytes_in_use']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
