"""Serving launcher: continuous-batching request-queue loop.

Drives ``repro.serve.scheduler.DecodeScheduler`` against a synthetic
arrival process (Poisson, or a trace file of ``arrival_s,max_new``
lines) and reports aggregate tokens/s, p50/p99 request latency, and
slot occupancy. ``--compare`` also runs the same workload through
back-to-back batch-synchronous ``engine.generate_batch_sync`` calls at
equal slot count, to show what continuous batching buys on
mixed-length traffic.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
        --smoke --slots 4 --requests 16 --rate 50 --compare

Add ``--kv paged`` to serve from the block-table KV cache
(``repro.serve.kv_cache``, DESIGN.md §8): cache memory is bounded by
tokens in flight instead of ``slots x max_len``, so mixed short/long
traffic fits more resident requests per byte — size the pool with
``--kv-block`` / ``--kv-blocks``. Greedy outputs are bit-identical to
the dense default. ``--attn-impl pallas`` (with ``--kv paged``) runs
decode through the gather-free Pallas paged-attention kernel
(DESIGN.md §8.1); the report line names the path that ACTUALLY ran —
``pallas-paged:interpret`` on CPU is a correctness fallback, not a
TPU number.

``--prefill chunked --chunk-tokens C`` turns admission from a
stop-the-world prefill into bounded per-step work (DESIGN.md §8.2):
prompts prefill inside the decode loop, ``C`` stream positions per
iteration interleaved with one decode token per running slot, so p99
inter-token latency for running slots stays flat while long prompts
stream in (``benchmarks/bench_chunked_prefill.py`` measures the
bound). The report line also names the prefill path that ran
(``flash-paged:*`` vs ``dense-bucketed``).

``--spec-k K`` (with ``--prefill chunked``) turns on in-graph
speculative decoding (DESIGN.md §8.4): every decode iteration drafts
``K`` candidate tokens per running slot — ``--spec-drafter ngram``
(default) looks the continuation up in the slot's own prompt + output,
``--spec-drafter model --draft-arch A`` decodes them from a small
draft model riding its own cache — and ONE verify forward through the
block table scores all ``K+1`` positions; the accepted prefix lands
in-graph, so accepted tokens cost one iteration instead of
``accepted+1``. Greedy outputs stay bit-identical; the report prints
accepted/drafted and the mean accept length.

``--early-exit --exit-threshold T`` turns on confidence-based
early-exit decode (DESIGN.md §8.6): each decode layer ends with a
logit-margin check through the shared unembedding, and rows whose
top-1/top-2 margin clears ``T`` stop running layers — the per-layer
loop is a ``core.while_loop`` over a per-row halt vector, and skipped
layers' K/V slots are filled from the halting layer's hidden state so
later tokens attend to a complete cache. ``T = inf`` (the default)
runs every layer and is bit-identical to the non-adaptive engine;
finite ``T`` trades fidelity for depth. ``--mod-capacity C`` adds a
mixture-of-depths router on every other layer (top-``C`` fraction of
tokens processed in training; learned-threshold routing in decode).
The report prints mean layers/token per request class, and
``--compare`` re-runs the workload with early exit off.

``--prefix-cache`` (with ``--prefill chunked --kv paged``) adds
content-addressed prefix caching (DESIGN.md §8.3): a hot prompt
prefills ONCE — later identical prompts map the cached blocks into
their own tables (refcounted, copy-on-write) and start prefilling at
their first uncached block. Pair with ``--prompt-pool P`` to generate
the repeated-prompt traffic it serves
(``benchmarks/bench_prefix_cache.py`` measures admission-to-first-
token and capacity at equal pool bytes).

``--disagg`` serves through prefill/decode disaggregation
(DESIGN.md §8.7): the device fleet is carved into a prefill slice and
a decode slice (``--prefill-devices N`` sizes the first; default
half), prompts chunk-prefill on the first while running slots decode
undisturbed on the second, and finished KV blocks ship slice-to-slice
asynchronously (``jax.device_put`` into the decode pool's sharding,
double-buffered under the next round's prefill chunk). The report
line names the transfer path that ran — ``device_put:dcn`` when
``repro.launch.distributed`` initialized a multi-process fleet,
``device_put:ics`` within one process, ``colocated`` for the
single-tier schedulers. On one device both tiers share it (no
protection, but bit-identical routing — CI's 8-virtual-device job
exercises the real 4+4 split).
"""

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.dist import sharding as sh
from repro.launch import distributed as dist_env
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model_zoo
from repro.serve import disagg as disagg_lib
from repro.serve import engine, sampling
from repro.serve import scheduler as sched_lib
from repro.serve import speculative as spec_lib


def build_workload(args, rng):
    """[(arrival_s, max_new)] sorted by arrival."""
    if args.trace:
        rows = []
        with open(args.trace) as f:
            for line in f:
                line = line.strip()
                if not line or line.startswith("#"):
                    continue
                t, m = line.split(",")
                rows.append((float(t), int(m)))
        return sorted(rows)
    # Poisson arrivals; alternate short/long max_new (mixed-length
    # traffic is where continuous batching pays).
    gaps = rng.exponential(1.0 / args.rate, size=args.requests)
    arrivals = np.cumsum(gaps)
    rows = [(float(arrivals[i]),
             args.max_new_short if i % 2 == 0 else args.max_new_long)
            for i in range(args.requests)]
    return rows


def build_prompts(args, cfg, n: int):
    """Request id -> (1, prompt_len) int32 prompt for ``n`` requests,
    from ``args.seed``. ``--prompt-pool P`` draws them from P distinct
    prompts (default: all distinct) — hot repeated prompts are the
    traffic ``--prefix-cache`` exists for."""
    rng = np.random.default_rng(args.seed)
    pool_n = args.prompt_pool or n
    pool = [rng.integers(2, cfg.vocab,
                         (1, args.prompt_len)).astype(np.int32)
            for _ in range(pool_n)]
    return {i: pool[i % pool_n] for i in range(n)}


def pctl(xs, q):
    return float(np.percentile(np.asarray(xs), q)) if len(xs) else 0.0


def run_continuous(args, cfg, params, workload):
    cap = max(m for _, m in workload)
    sp = sampling.SamplingParams(temperature=args.temperature,
                                 top_k=args.top_k)
    spec, draft_params, draft_cfg = None, None, None
    if args.spec_k:
        spec = spec_lib.SpecConfig(k=args.spec_k,
                                   drafter=args.spec_drafter,
                                   ngram=args.spec_ngram)
        if args.spec_drafter == "model":
            if not args.draft_arch:
                raise SystemExit("--spec-drafter model needs --draft-arch")
            draft_cfg = get_config(args.draft_arch, smoke=args.smoke)
            draft_params = model_zoo.init_params(draft_cfg,
                                                 jax.random.PRNGKey(1))
    sched = sched_lib.DecodeScheduler(
        params, cfg, n_slots=args.slots, prompt_len=args.prompt_len,
        max_new_cap=cap, eos_id=args.eos_id, sampling=sp, seed=args.seed,
        kv=args.kv, kv_block=args.kv_block, kv_blocks=args.kv_blocks,
        prefill=args.prefill, chunk_tokens=args.chunk_tokens,
        prefix_cache=args.prefix_cache, speculative=spec,
        draft_params=draft_params, draft_cfg=draft_cfg)
    prompts = build_prompts(args, cfg, len(workload))
    # Warm compiles outside the timed window (prefill + both step modes).
    t_warm = time.perf_counter()
    sched.warmup()
    compile_s = time.perf_counter() - t_warm

    arrival_wall = {}
    finish_wall = {}
    req_depth = {}
    req_tokens = {}
    t0 = time.perf_counter()
    next_req = 0
    idle_s = 0.0          # open-loop arrival gaps: excluded from tok/s
    while len(finish_wall) < len(workload):
        now = time.perf_counter() - t0
        while next_req < len(workload) and workload[next_req][0] <= now:
            rid = sched.submit(prompts[next_req],
                               max_new=workload[next_req][1],
                               request_id=next_req)
            arrival_wall[rid] = workload[next_req][0]
            next_req += 1
        if sched.pending == 0:
            # idle until the next arrival (not the server's doing)
            if next_req < len(workload):
                gap = max(0.0, workload[next_req][0] - now)
                time.sleep(gap)
                idle_s += gap
            continue
        # expect_arrivals: don't drain past upcoming arrivals — a
        # request landing mid-segment should find freed slots promptly
        for f in sched.step(expect_arrivals=next_req < len(workload)):
            finish_wall[f.request_id] = time.perf_counter() - t0
            req_depth[f.request_id] = f.mean_depth
            req_tokens[f.request_id] = f.tokens
    wall = time.perf_counter() - t0
    busy = max(wall - idle_s, 1e-9)
    lat = [finish_wall[r] - arrival_wall[r] for r in finish_wall]
    toks = sched.tokens_emitted
    return {"wall_s": wall, "busy_s": busy, "tok_s": toks / busy,
            "p50_s": pctl(lat, 50), "p99_s": pctl(lat, 99),
            "occupancy": sched.occupancy, "steps": sched.total_steps,
            "tokens": toks, "attn_impl": sched.attn_impl,
            "prefill_impl": sched.prefill_impl,
            "transfer_impl": sched.transfer_impl,
            "prefix_hit_blocks": sched.prefix_hit_blocks,
            "prefix_evictions": sched.prefix_evictions,
            "accepted_tokens": sched.accepted_tokens,
            "drafted_tokens": sched.drafted_tokens,
            "accept_rate": sched.accept_rate,
            "mean_accept_len": sched.mean_accept_len,
            "mean_depth": sched.mean_depth,
            "req_depth": req_depth, "req_tokens": req_tokens,
            "compile_s": compile_s}


def run_disagg(args, cfg, params, workload):
    """Two-tier prefill/decode disaggregation (DESIGN.md §8.7).

    Carves the fleet into disjoint prefill/decode submeshes when more
    than one device is visible (``--prefill-devices`` sizes the
    prefill slice; default half) and drives the same arrival loop as
    :func:`run_continuous` through ``DisaggScheduler`` — long-prompt
    admission burns prefill-slice FLOPs only, so running slots'
    inter-token latency stays flat (``benchmarks/bench_disagg.py``
    measures the bound against colocated chunked prefill)."""
    dist_env.init_distributed()  # no-op single-process; DCN otherwise
    pf_mesh = de_mesh = None
    n_dev = jax.device_count()
    if n_dev > 1:
        n_pf = args.prefill_devices or n_dev // 2
        pf_devs, de_devs = sh.carve_slices(n_pf)
        pf_mesh = sh.slice_mesh(pf_devs)
        de_mesh = sh.slice_mesh(de_devs)
    cap = max(m for _, m in workload)
    sp = sampling.SamplingParams(temperature=args.temperature,
                                 top_k=args.top_k)
    spec = None
    if args.spec_k:
        if args.spec_drafter == "model":
            raise SystemExit("--disagg composes with the ngram "
                             "drafter only (a draft model would need "
                             "its own cache shipped across the slice "
                             "boundary)")
        spec = spec_lib.SpecConfig(k=args.spec_k,
                                   drafter=args.spec_drafter,
                                   ngram=args.spec_ngram)
    sched = disagg_lib.DisaggScheduler(
        params, cfg,
        n_prefill_slots=args.prefill_slots or args.slots,
        n_decode_slots=args.slots, prompt_len=args.prompt_len,
        max_new_cap=cap, eos_id=args.eos_id, sampling=sp,
        prefill_mesh=pf_mesh, decode_mesh=de_mesh, seed=args.seed,
        kv_block=args.kv_block, decode_kv_blocks=args.kv_blocks,
        chunk_tokens=args.chunk_tokens,
        prefix_cache=args.prefix_cache, speculative=spec,
        segment_steps=args.segment_steps)
    prompts = build_prompts(args, cfg, len(workload))
    t_warm = time.perf_counter()
    sched.warmup()
    compile_s = time.perf_counter() - t_warm

    arrival_wall = {}
    finish_wall = {}
    req_tokens = {}
    t0 = time.perf_counter()
    next_req = 0
    idle_s = 0.0
    while len(finish_wall) < len(workload):
        now = time.perf_counter() - t0
        while next_req < len(workload) and workload[next_req][0] <= now:
            rid = sched.submit(prompts[next_req],
                               max_new=workload[next_req][1],
                               request_id=next_req)
            arrival_wall[rid] = workload[next_req][0]
            next_req += 1
        if sched.pending == 0:
            if next_req < len(workload):
                gap = max(0.0, workload[next_req][0] - now)
                time.sleep(gap)
                idle_s += gap
            continue
        for f in sched.step(expect_arrivals=next_req < len(workload)):
            finish_wall[f.request_id] = time.perf_counter() - t0
            req_tokens[f.request_id] = f.tokens
    wall = time.perf_counter() - t0
    kv_key = engine.kv_key(cfg)

    def pool_devices(tier):
        return sorted(d.id for d in tier.pool.cache[kv_key].k_pool.devices())

    busy = max(wall - idle_s, 1e-9)
    lat = [finish_wall[r] - arrival_wall[r] for r in finish_wall]
    toks = sched.tokens_emitted
    return {"wall_s": wall, "busy_s": busy, "tok_s": toks / busy,
            "p50_s": pctl(lat, 50), "p99_s": pctl(lat, 99),
            "tokens": toks, "steps": sched.total_steps,
            "prefill_steps": sched.prefill_steps,
            "attn_impl": sched.attn_impl,
            "prefill_impl": sched.prefill_impl,
            "transfer_impl": sched.transfer_impl,
            "transfers": sched.transfers,
            "transfer_bytes": sched.transfer_bytes,
            "preemptions": sched.preemptions,
            "replay_mismatches": sched.replay_mismatches,
            "prefill_devices": len(pf_mesh.devices.flat) if pf_mesh else 1,
            "decode_devices": len(de_mesh.devices.flat) if de_mesh else 1,
            "prefill_pool_devices": pool_devices(sched.prefill),
            "decode_pool_devices": pool_devices(sched.decode),
            "req_tokens": req_tokens, "compile_s": compile_s}


def run_stream(args, cfg, params, workload):
    """Asyncio streaming front-end over the SLO scheduler: every Nth
    request (``--hi-every``) is submitted as the *interactive* class,
    the rest as *batch*; under overload the SLO layer preempts batch
    residents so interactive TTFT holds (DESIGN.md §8.5). Reports
    per-class p50/p99 TTFT/ITL from ``SLOScheduler.json_summary``."""
    import asyncio

    from repro.serve import frontend as fe
    from repro.serve import slo as slo_lib

    cap = max(m for _, m in workload)
    sp = sampling.SamplingParams(temperature=args.temperature,
                                 top_k=args.top_k)
    sched = sched_lib.DecodeScheduler(
        params, cfg, n_slots=args.slots, prompt_len=args.prompt_len,
        max_new_cap=cap, eos_id=args.eos_id, sampling=sp, seed=args.seed,
        kv=args.kv, kv_block=args.kv_block, kv_blocks=args.kv_blocks,
        prefill=args.prefill, chunk_tokens=args.chunk_tokens,
        prefix_cache=args.prefix_cache)
    sched.warmup()
    slo = slo_lib.SLOScheduler(sched, segment_steps=args.segment_steps)
    front = fe.StreamingFrontend(slo, max_inflight=args.max_inflight)
    prompts = build_prompts(args, cfg, len(workload))

    async def client(i, arrival, max_new):
        await asyncio.sleep(arrival)
        klass = ("interactive" if args.hi_every
                 and i % args.hi_every == 0 else "batch")
        toks = 0
        async for ev in front.stream(prompts[i], max_new=max_new,
                                     slo_class=klass, request_id=i):
            if ev["event"] == "token":
                toks += len(ev["tokens"])
        return toks

    async def drive():
        return await asyncio.gather(*[
            asyncio.create_task(client(i, a, m))
            for i, (a, m) in enumerate(workload)])

    t0 = time.perf_counter()
    tok_counts = asyncio.run(drive())
    wall = time.perf_counter() - t0
    summary = slo.json_summary()
    summary["wall_s"] = wall
    summary["tokens"] = int(sum(tok_counts))
    return summary


def run_batch_sync(args, cfg, params, workload):
    """Back-to-back batch-synchronous generate at equal slot count.

    Same cache layout and attention path as the continuous run
    (``--kv`` / ``--attn-impl`` thread through), so the printed ratio
    isolates the scheduling policy; the per-call pool is sized
    dense-equivalent (``--kv-blocks`` under-provisioning is a
    *scheduler* capacity knob and has no batch-sync analogue)."""
    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(rng.integers(
        2, cfg.vocab, (len(workload), args.prompt_len)), jnp.int32)
    gens = {}

    warm = prompts[jnp.zeros(args.slots, jnp.int32)]  # (slots, L): the
    # timed loop always calls with a padded full-slots batch

    def gen_for(max_new):
        if max_new not in gens:
            gens[max_new] = jax.jit(lambda p, t: engine.generate_batch_sync(
                p, cfg, t, max_new=max_new, eos_id=args.eos_id,
                kv_impl=args.kv, kv_block=args.kv_block))
            _ = gens[max_new](params, warm)  # compile at the timed shape
        return gens[max_new]

    batches = [list(range(i, min(i + args.slots, len(workload))))
               for i in range(0, len(workload), args.slots)]
    for b in batches:  # warm every needed compile
        gen_for(max(workload[i][1] for i in b))

    toks = 0
    attn_impl = ""
    t0 = time.perf_counter()
    for b in batches:
        cap = max(workload[i][1] for i in b)
        idx = b + [b[-1]] * (args.slots - len(b))    # pad last batch
        res = gen_for(cap)(params, prompts[jnp.asarray(idx)])
        jax.block_until_ready(res.tokens)
        attn_impl = res.attn_impl
        toks += int(sum(min(int(res.lengths[j]), workload[i][1])
                        for j, i in enumerate(b)))
    wall = time.perf_counter() - t0
    return {"wall_s": wall, "tok_s": toks / wall, "tokens": toks,
            "attn_impl": attn_impl}


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=50.0,
                    help="Poisson arrival rate (req/s)")
    ap.add_argument("--trace", default=None,
                    help="CSV trace: arrival_s,max_new per line")
    ap.add_argument("--max-new-short", type=int, default=8)
    ap.add_argument("--max-new-long", type=int, default=32)
    ap.add_argument("--eos-id", type=int, default=1)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kv", choices=("dense", "paged"), default="dense",
                    help="KV-cache layout: 'paged' bounds cache memory "
                         "by tokens in flight (block tables, "
                         "DESIGN.md §8) instead of slots x max_len")
    ap.add_argument("--kv-block", type=int, default=16,
                    help="paged cache block size (tokens)")
    ap.add_argument("--kv-blocks", type=int, default=None,
                    help="paged pool capacity in blocks (default: "
                         "dense-equivalent)")
    ap.add_argument("--attn-impl", choices=("xla", "pallas"), default=None,
                    help="decode attention path: 'pallas' + --kv paged "
                         "runs the gather-free paged-attention kernel "
                         "(compiled on TPU, interpret elsewhere); "
                         "default keeps the config's setting")
    ap.add_argument("--prefill", choices=("oneshot", "chunked"),
                    default="oneshot",
                    help="admission mode: 'chunked' prefills prompts "
                         "INSIDE the decode loop (<= --chunk-tokens "
                         "stream positions per step, interleaved with "
                         "one decode token per running slot), so a "
                         "long prompt never stalls running slots; with "
                         "--attn-impl pallas + --kv paged the chunk "
                         "attention streams prior K/V through the "
                         "block table (kernels.flash_prefill)")
    ap.add_argument("--chunk-tokens", type=int, default=16,
                    help="chunked-prefill chunk size (smaller = tighter "
                         "inter-token latency bound, more prefill "
                         "iterations per prompt)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="content-addressed prefix caching (requires "
                         "--prefill chunked --kv paged): a repeated "
                         "prompt's full blocks are MAPPED into the new "
                         "row's table (copy-on-write shared, refcounted) "
                         "and its prefill starts at the first uncached "
                         "block; greedy outputs stay bit-identical")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: draft this many "
                         "candidate tokens per decode iteration and "
                         "verify them all in ONE target forward "
                         "(requires --prefill chunked; 0 = off); "
                         "greedy outputs stay bit-identical")
    ap.add_argument("--spec-drafter", choices=("ngram", "model"),
                    default="ngram",
                    help="draft source: 'ngram' looks the continuation "
                         "up in the slot's own prompt + emitted tokens "
                         "(no extra model); 'model' decodes drafts from "
                         "--draft-arch riding its own KV cache")
    ap.add_argument("--spec-ngram", type=int, default=2,
                    help="n-gram drafter match length")
    ap.add_argument("--draft-arch", default=None,
                    help="draft model architecture for --spec-drafter "
                         "model (must share the target's vocab)")
    ap.add_argument("--early-exit", action="store_true",
                    help="confidence-based early-exit decode "
                         "(DESIGN.md §8.6): rows whose top-1/top-2 "
                         "logit margin clears --exit-threshold stop "
                         "running layers; skipped layers' K/V is "
                         "filled from the halting layer's hidden "
                         "state; threshold=inf is bit-identical to "
                         "the non-adaptive engine")
    ap.add_argument("--exit-threshold", type=float, default=float("inf"),
                    help="early-exit logit-margin threshold (inf = "
                         "never exit early; smaller = shallower)")
    ap.add_argument("--exit-min-layers", type=int, default=1,
                    help="layers every token must run before the "
                         "halt check can fire")
    ap.add_argument("--mod-capacity", type=float, default=0.0,
                    help="mixture-of-depths: fraction of tokens each "
                         "routed (every --mod-every'th) layer "
                         "processes in training; decode routes by a "
                         "learned per-token gate (0 = off; adds "
                         "router params, so the checkpoint changes)")
    ap.add_argument("--mod-every", type=int, default=2,
                    help="route every Nth layer when --mod-capacity "
                         "is set (unrouted layers process all tokens)")
    ap.add_argument("--prompt-pool", type=int, default=0,
                    help="draw the workload's prompts from this many "
                         "distinct prompts (0 = all distinct); the "
                         "repeated-prompt traffic --prefix-cache serves")
    ap.add_argument("--disagg", action="store_true",
                    help="prefill/decode disaggregation (DESIGN.md "
                         "§8.7): carve the fleet into a prefill slice "
                         "and a decode slice, chunk-prefill prompts on "
                         "the first, ship finished KV blocks to the "
                         "second asynchronously; implies --kv paged "
                         "--prefill chunked on both tiers")
    ap.add_argument("--prefill-devices", type=int, default=0,
                    help="--disagg: devices in the prefill slice "
                         "(0 = half the fleet); the rest decode")
    ap.add_argument("--prefill-slots", type=int, default=0,
                    help="--disagg: prefill-tier slot count "
                         "(0 = same as --slots)")
    ap.add_argument("--compare", action="store_true",
                    help="also run the batch-synchronous baseline; with "
                         "--spec-k / --prefix-cache ALSO re-runs the "
                         "continuous path with that feature off and "
                         "prints both paths' accept/hit stats side by "
                         "side")
    ap.add_argument("--stream", action="store_true",
                    help="serve through the asyncio streaming front-end "
                         "(repro.serve.frontend) over the SLO scheduler: "
                         "per-request SSE-shaped token streams, priority "
                         "classes (--hi-every), block-level preemption "
                         "under overload; reports per-class p50/p99 "
                         "TTFT/ITL instead of aggregate latency")
    ap.add_argument("--segment-steps", type=int, default=8,
                    help="--stream: in-graph iterations per SLO round "
                         "(token surfacing / preemption granularity)")
    ap.add_argument("--max-inflight", type=int, default=64,
                    help="--stream: admission-semaphore width "
                         "(backpressure at the front door)")
    ap.add_argument("--hi-every", type=int, default=4,
                    help="--stream: every Nth request is the "
                         "'interactive' (preempting) class; 0 = all "
                         "batch")
    return ap


def model_config(args):
    """The served ``ModelConfig``: the arch's config with the
    attention-path and adaptive-depth options applied."""
    cfg = get_config(args.arch, smoke=args.smoke)
    if args.attn_impl is not None:
        cfg = dataclasses.replace(cfg, attn_impl=args.attn_impl)
    if args.early_exit or args.mod_capacity:
        cfg = dataclasses.replace(
            cfg, early_exit=args.early_exit,
            exit_threshold=args.exit_threshold,
            exit_min_layers=args.exit_min_layers,
            mod_capacity=args.mod_capacity, mod_every=args.mod_every)
    return cfg


def main():
    enable_compile_cache()
    args = build_parser().parse_args()
    cfg = model_config(args)
    params = model_zoo.init_params(cfg, jax.random.PRNGKey(0))
    workload = build_workload(args, np.random.default_rng(args.seed))

    if args.stream:
        s = run_stream(args, cfg, params, workload)
        print(f"[serve] stream: {s['tokens']} tokens in "
              f"{s['wall_s']:.2f}s | {s['preemptions']} preemptions, "
              f"{s['replay_mismatches']} replay mismatches, "
              f"{s['completed']} completed "
              f"({s['total_steps']} device steps, "
              f"segment={s['segment_steps']})")
        for name, c in s["classes"].items():
            tw, iw = c["ttft_wall_s"], c["itl_wall_s"]
            ts, is_ = c["ttft_steps"], c["itl_steps"]
            print(f"[serve]   {name} (prio {c['priority']}): "
                  f"{c['completed']} done, "
                  f"{c['preempted_times']} preempted | "
                  f"TTFT p50 {ts['p50'] or 0:.0f}/p99 "
                  f"{ts['p99'] or 0:.0f} steps "
                  f"({(tw['p50'] or 0) * 1e3:.0f}/"
                  f"{(tw['p99'] or 0) * 1e3:.0f}ms) | "
                  f"ITL p50 {is_['p50'] or 0:.1f}/p99 "
                  f"{is_['p99'] or 0:.1f} steps "
                  f"({(iw['p50'] or 0) * 1e3:.0f}/"
                  f"{(iw['p99'] or 0) * 1e3:.0f}ms)")
        return

    if args.disagg:
        d = run_disagg(args, cfg, params, workload)
        print(f"[serve] disagg {d['prefill_devices']}+"
              f"{d['decode_devices']} (decode {d['attn_impl']}, "
              f"prefill {d['prefill_impl']}, "
              f"transfer {d['transfer_impl']}): "
              f"{d['tokens']} tokens, "
              f"{d['wall_s']:.2f}s wall ({d['busy_s']:.2f}s busy) -> "
              f"{d['tok_s']:.1f} tok/s | "
              f"latency p50 {d['p50_s'] * 1e3:.0f}ms "
              f"p99 {d['p99_s'] * 1e3:.0f}ms | "
              f"{d['steps']} decode steps + "
              f"{d['prefill_steps']} prefill-slice steps")
        print(f"[serve]   shipped {d['transfers']} KV shipments "
              f"({d['transfer_bytes'] / 1e6:.2f} MB) | "
              f"{d['preemptions']} preemptions, "
              f"{d['replay_mismatches']} replay mismatches")
        return

    cont = run_continuous(args, cfg, params, workload)
    print(f"[serve] continuous (decode {cont['attn_impl']}, "
          f"prefill {cont['prefill_impl']}, "
          f"transfer {cont['transfer_impl']}): "
          f"{cont['tokens']} tokens, "
          f"{cont['wall_s']:.2f}s wall ({cont['busy_s']:.2f}s busy) -> "
          f"{cont['tok_s']:.1f} tok/s | "
          f"latency p50 {cont['p50_s'] * 1e3:.0f}ms "
          f"p99 {cont['p99_s'] * 1e3:.0f}ms | "
          f"occupancy {cont['occupancy'] * 100:.0f}% "
          f"({cont['steps']} device steps) | "
          f"warmup compile {cont['compile_s']:.2f}s")
    if args.early_exit:
        # per-class mean layers/token: group requests by their
        # max_new budget (the workload's short/long classes)
        by_class = {}
        for rid, d in cont["req_depth"].items():
            by_class.setdefault(workload[rid][1], []).append(d)
        per = ", ".join(
            f"max_new={m}: {np.mean(ds):.2f}"
            for m, ds in sorted(by_class.items()))
        print(f"[serve] adaptive depth (threshold="
              f"{args.exit_threshold:g}): "
              f"{cont['mean_depth']:.2f} mean layers/token of "
              f"{cfg.n_layers} | per class: {per}")
    if args.prefix_cache:
        print(f"[serve] prefix cache: {cont['prefix_hit_blocks']} "
              f"blocks served from cache, "
              f"{cont['prefix_evictions']} evictions")
    if args.spec_k:
        print(f"[serve] speculative (k={args.spec_k}, "
              f"{args.spec_drafter}): "
              f"{cont['accepted_tokens']}/{cont['drafted_tokens']} "
              f"drafts accepted "
              f"({cont['accept_rate'] * 100:.0f}%), "
              f"mean accept length "
              f"{cont['mean_accept_len']:.2f}")
    if args.compare:
        if args.spec_k or args.prefix_cache or args.early_exit:
            # feature-off continuous baseline: same scheduler, same
            # workload, spec/prefix/early-exit off — the side-by-side
            # isolates what the feature buys (the batch-sync baseline
            # below can't run these features, so comparing only
            # against it silently dropped these stats)
            off = argparse.Namespace(**vars(args))
            off.spec_k, off.prefix_cache = 0, False
            off.early_exit = False
            # early_exit is a model-config knob, not just a scheduler
            # one; router params (mod_capacity) are shape-compatible
            # either way, so the same params serve both runs
            base_cfg = (dataclasses.replace(cfg, early_exit=False)
                        if args.early_exit else cfg)
            base = run_continuous(off, base_cfg, params, workload)
            feats = "+".join(
                (["spec-k%d" % args.spec_k] if args.spec_k else [])
                + (["prefix-cache"] if args.prefix_cache else [])
                + (["early-exit@%g" % args.exit_threshold]
                   if args.early_exit else []))
            print(f"[serve] continuous feature comparison "
                  f"({feats} vs off):")
            rows = [("tok/s", f"{cont['tok_s']:.1f}",
                     f"{base['tok_s']:.1f}"),
                    ("p99 latency", f"{cont['p99_s'] * 1e3:.0f}ms",
                     f"{base['p99_s'] * 1e3:.0f}ms"),
                    ("device steps", str(cont["steps"]),
                     str(base["steps"]))]
            if args.spec_k:
                rows += [("accept rate",
                          f"{cont['accept_rate'] * 100:.0f}% "
                          f"({cont['accepted_tokens']}/"
                          f"{cont['drafted_tokens']})", "n/a"),
                         ("mean accept len",
                          f"{cont['mean_accept_len']:.2f}", "n/a")]
            if args.prefix_cache:
                rows += [("prefix hit blocks",
                          str(cont["prefix_hit_blocks"]), "n/a"),
                         ("prefix evictions",
                          str(cont["prefix_evictions"]), "n/a")]
            if args.early_exit:
                rows += [("mean layers/token",
                          f"{cont['mean_depth']:.2f}",
                          f"{base['mean_depth']:.2f}")]
            for name, on_v, off_v in rows:
                print(f"[serve]   {name:>18}: {on_v:>16} | "
                      f"{off_v:>10} (off)")
        sync = run_batch_sync(args, cfg, params, workload)
        print(f"[serve] batch-sync ({sync['attn_impl']}; offline, no "
              f"arrival gating): "
              f"{sync['tokens']} tokens in {sync['wall_s']:.2f}s -> "
              f"{sync['tok_s']:.1f} tok/s")
        # both rates are busy-time rates, so the ratio is arrival-free
        print(f"[serve] continuous/batch-sync busy tokens/s ratio: "
              f"{cont['tok_s'] / max(sync['tok_s'], 1e-9):.2f}x")


if __name__ == "__main__":
    main()
