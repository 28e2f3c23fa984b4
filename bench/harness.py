"""One run of one cell: set-up, a measured window, the check, the line.

The cell (``BENCHMARK.json``) names a configuration and a traffic mix;
both are files found by name (``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``), and so are the configuration's
family and reference (``bench/configs/<family>.py``, named in the
configuration file), the metric readers (``bench/metrics/<metric>.py``;
``mfu.batch`` is read by ``mfu.py`` unless it has a file of its own)
and the cell's comparison limits (``bench/limits/<cell>.json``).
Nothing here names a cell, a configuration or a mix.

The timed path is the program's streaming serving stack:
``serve.frontend.StreamingFrontend.stream`` over ``serve.slo``'s
``SLOScheduler`` over ``serve.scheduler.DecodeScheduler`` with paged KV,
chunked prefill and the Pallas kernels. Every request is an asyncio
client released by the open-loop generator at its due time; the client
stamps each ``token`` and ``done`` event on the benchmark's clock.
"""

from __future__ import annotations

import asyncio
import dataclasses
import gc
import json
import pathlib
import shutil
import sys
import time
from typing import Dict, List, Optional

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
from loader import family, load_module  # noqa: E402

TRACE_DIR = ROOT / "bench_out" / "trace"
SAMPLE = 8                 # finished requests the reference re-reads
# the SLO layer's host counters of its submit lock, read at the edges
SUBMIT_LOCK = ("submit_lock_wait_s", "submits")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ---------------- files found by name ----------------------------------

def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_of(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_entry(spec: dict, name: str) -> dict:
    for c in spec["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def load_config(spec: dict, name: str) -> dict:
    return json.loads((ROOT / config_entry(spec, name)["file"]).read_text())


def metrics_of(spec: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics this cell reports: end-to-end ones with ``--trace
    0``, per-layer ones with ``--trace 1``."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def metric_reader(name: str):
    """The reader of metric ``name``: ``bench/metrics/<name>.py``, else
    the file of the name before its first dot, so that ``mfu.serve``
    and ``mfu.batch`` (one quantity, split by the end-to-end metric it
    moves) share ``mfu.py``."""
    for stem in (name, name.split(".")[0]):
        path = BENCH / "metrics" / f"{stem}.py"
        if path.is_file():
            return load_module(path)
    raise FileNotFoundError(f"no reader for metric {name!r} in "
                            f"{BENCH / 'metrics'}")


def load_limits(cell: str) -> dict:
    path = BENCH / "limits" / f"{cell}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no comparison limits {path}")
    return json.loads(path.read_text())


# ---------------- device ----------------------------------------------

def devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found {devs[0].platform} devices, no TPU; the "
                     f"benchmark never falls back to another backend")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


_COUNTER = None


def compile_counter() -> "CompileCounter":
    """The process's one counter (listeners cannot be removed)."""
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = CompileCounter()
    return _COUNTER


class CompileCounter:
    """Counts jit traces and backend compiles while ``on``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax
        self.on = False
        self.n = 0
        self.at_window = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if self.on and event in self.EVENTS:
            self.n += 1


# ---------------- the system under test --------------------------------

def counted_slo(slo_lib):
    """``SLOScheduler`` that logs the inner scheduler's counters after
    every round and folds them in before each ``reset_stats()`` (which
    zeroes them whenever a drained scheduler takes new work). With
    ``read_slot_steps`` (traced runs) it also logs the program's own
    lifetime counters, ``inner.counters()``, once at its start and after
    every round, where the scheduler has them."""
    import jax

    class Counted(slo_lib.SLOScheduler):
        def __init__(self, inner, *, segment_steps, read_slot_steps):
            super().__init__(inner, segment_steps=segment_steps)
            self.read_slot_steps = read_slot_steps
            self.folded = np.zeros(3, np.int64)
            self.log: List[tuple] = []
            self.program_log: Optional[List[tuple]] = (
                [] if read_slot_steps and hasattr(inner, "counters")
                else None)
            self.stepping = False
            reset = inner.reset_stats

            def fold_then_reset():
                self.folded += self._raw()
                reset()
            inner.reset_stats = fold_then_reset
            self._log_program()

        def _raw(self):
            inner = self.inner
            slot = (int(inner.pool.slot_steps) if self.read_slot_steps
                    else 0)
            return np.array([inner.total_steps, inner.prefix_hit_blocks,
                             slot], np.int64)

        def _log_program(self):
            if self.program_log is not None:
                self.program_log.append((time.perf_counter(),
                                         self.inner.counters()))

        def counters(self) -> np.ndarray:
            """(loop iterations, prefix-hit blocks, busy slot-steps)."""
            return self.folded + self._raw()

        def step(self):
            self.stepping = True
            try:
                with jax.profiler.TraceAnnotation("bench.slo_step"):
                    events = super().step()
                self.log.append((time.perf_counter(), self.counters()))
                self._log_program()
            finally:
                self.stepping = False
            return events

    return Counted


def host_reading(obj, *names) -> Optional[dict]:
    """``{name: obj.name}``, or None where ``obj`` lacks one of them (a
    program from before it had them)."""
    if not all(hasattr(obj, n) for n in names):
        return None
    return {n: getattr(obj, n) for n in names}


def blocks_per_row(sched) -> Optional[int]:
    """Width of the paged KV cache's block table, None for a program or
    cache without one."""
    cache = getattr(getattr(sched, "pool", None), "cache", None)
    for entry in (cache.values() if isinstance(cache, dict) else [cache]):
        if hasattr(entry, "blocks_per_row"):
            return int(entry.blocks_per_row)
    return None


@dataclasses.dataclass
class Client:
    rid: int
    due: float                     # absolute, benchmark clock
    prompt: np.ndarray             # (L,) token ids
    max_new: int
    released: Optional[float] = None
    events: List[tuple] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    done: Optional[float] = None
    error: Optional[str] = None

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)


async def _client(front, req, cl: Client, klass: str):
    cl.released = time.perf_counter()
    try:
        async for ev in front.stream(req.prompt, max_new=req.max_new,
                                     slo_class=klass, request_id=req.rid):
            t = time.perf_counter()
            if ev["event"] == "token":
                cl.events.append((t, len(ev["tokens"])))
                cl.tokens.extend(int(x) for x in ev["tokens"])
            elif ev["event"] == "done":
                cl.done = t
    except asyncio.CancelledError:
        raise
    except Exception as exc:          # a failed request, counted
        cl.error = repr(exc)


async def _serve(front, slo, sched_obj, mix, counter, trace: bool):
    """The open loop: ramp, window, stop."""
    default_class = mix["serve"]["slo_class"]
    clients: Dict[int, Client] = {}
    tasks = []
    late = []
    t0 = time.perf_counter()
    w0 = t0 + sched_obj.window_start_s
    w1 = w0 + sched_obj.window_s
    counter.on = True
    started = {"trace": None}
    lock = []                  # the SLO layer's submit-lock readings

    async def window_edges():
        await asyncio.sleep(max(0.0, w0 - time.perf_counter()))
        counter.at_window = counter.n
        if trace:
            lock.append(host_reading(slo, *SUBMIT_LOCK))
            import jax
            jax.profiler.start_trace(str(TRACE_DIR))
            started["trace"] = jax.profiler.TraceAnnotation("bench.window")
            started["trace"].__enter__()

    edge = asyncio.create_task(window_edges())
    for req in sched_obj.requests:
        due = t0 + req.due_s
        if due >= w1:
            break
        delay = due - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        now = time.perf_counter()
        late.append(now - due)
        cl = Client(req.rid, due, req.prompt[0], req.max_new)
        clients[req.rid] = cl
        klass = req.slo_class or default_class
        tasks.append(asyncio.create_task(_client(front, req, cl, klass)))
    await asyncio.sleep(max(0.0, w1 - time.perf_counter()))
    await edge
    counter.on = False
    if trace:
        lock.append(host_reading(slo, *SUBMIT_LOCK))
    if trace and started["trace"]:
        import jax
        started["trace"].__exit__(None, None, None)
        jax.profiler.stop_trace()
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    while front.inflight or slo.stepping:      # let the engine park
        await asyncio.sleep(0.01)
    edges = lock if len(lock) == 2 and None not in lock else None
    return clients, late, (t0, w0, w1), edges


# ---------------- the run ----------------------------------------------

def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, control: bool = False,
             rate: Optional[float] = None,
             rehearsal: Optional[dict] = None) -> tuple:
    """Run one cell once; return the result line (a dict) and the
    run's record, which the metric readers read.

    ``control`` puts the fp8 reference in the program's place for the
    comparison (see ``check``): ``correct`` is then the control's, and
    the program's own checks come under ``program_checks``. The
    benchmark's own runs never set it.

    ``rehearsal`` (tests only) replaces the configuration, mix and
    limits and skips the look for a chip, so that the rest of a run can
    be driven on the CPU at a small size."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import model_zoo
    from repro.serve import frontend as fe_lib
    from repro.serve import sampling as sampling_lib
    from repro.serve import scheduler as sched_lib
    from repro.serve import slo as slo_lib

    import peaks as peaks_lib
    import traffic as traffic_lib
    import weights as weights_lib

    spec = load_spec()
    cell = cell_of(spec, cell_name)
    if rehearsal is None:
        devs = devices(cell["chips"])
        peak = peaks_lib.peaks(devs[0].device_kind)
        c = load_config(spec, cell["config"])
        mix = traffic_lib.load_mix(cell["traffic"])
        limits = load_limits(cell_name)
    else:                         # a CPU test drives the rest of a run
        devs = jax.devices()[:1]
        peak = peaks_lib.PEAKS["TPU v5 lite"]
        c, mix, limits = (rehearsal["config"], rehearsal["mix"],
                          rehearsal["limits"])
    dev = devs[0]
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = compile_counter()
    counter.n = counter.at_window = 0
    if rate is not None:
        mix["arrivals"]["rate_per_s"] = rate
    ref = load_module(BENCH / "configs" / c["reference"])
    say(f"cell {cell_name}, seed {seed}, {seconds} s, trace {int(trace)}, "
        f"compile cache {cache_dir}")
    say(f"device platform {dev.platform}, kind {dev.device_kind}, "
        f"count {len(devs)}")

    cfg = family(c).program_config(c)
    abstract = model_zoo.abstract_params(cfg)
    t = time.perf_counter()
    params = weights_lib.build(abstract, c, seed)
    jax.block_until_ready(params)
    t_weights = time.perf_counter() - t
    sched_obj = traffic_lib.schedule(mix, c["vocab_size"], seed, seconds)

    s = mix["serve"]
    sched = sched_lib.DecodeScheduler(
        params, cfg, n_slots=s["slots"],
        prompt_len=traffic_lib.max_prompt_len(mix),
        max_new_cap=traffic_lib.max_new_cap(mix), eos_id=-1,
        sampling=sampling_lib.SamplingParams(),
        seed=int(seed % (2**31 - 1)), kv="paged", kv_block=s["kv_block"],
        kv_blocks=s["kv_blocks"], prefill="chunked",
        chunk_tokens=s["chunk_tokens"], prefix_cache=s["prefix_cache"])
    say(f"paths: decode {sched.attn_impl}, prefill {sched.prefill_impl}")
    if rehearsal is None and (
            sched.attn_impl != "pallas-paged:compiled"
            or sched.prefill_impl != "flash-paged:compiled"):
        raise RuntimeError("the Pallas kernels are not the compiled path")
    t = time.perf_counter()
    sched.warmup()
    slo = counted_slo(slo_lib)(sched, segment_steps=s["segment_steps"],
                               read_slot_steps=trace)
    sched.reset_stats()                  # compiles the counter reset too
    front = fe_lib.StreamingFrontend(slo, max_inflight=s["max_inflight"])
    t_warm = time.perf_counter() - t

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    clients, late, (t0, w0, w1), lock_edges = asyncio.run(
        _serve(front, slo, sched_obj, mix, counter, trace))
    setup_s = w0 - t_start
    mem_peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    log = list(slo.log)
    program_log = slo.program_log
    stamps = (slo.request_stamps()
              if trace and hasattr(slo, "request_stamps") else None)
    cache_bytes = sched.cache_bytes()
    bpr = blocks_per_row(sched)
    del front, slo, sched, sched_obj
    gc.collect()

    say(f"set-up {setup_s:.3f} s: weights {t_weights:.3f} s, warm-up "
        f"{t_warm:.3f} s, ramp {w0 - t0:.3f} s")
    say(f"compilations inside the window: {counter.n - counter.at_window}"
        f" (during the ramp: {counter.at_window})")
    lat = np.asarray(late)
    say(f"generator lateness p95 {np.percentile(lat, 95) * 1e3:.3f} ms, "
        f"max {lat.max() * 1e3:.3f} ms over {len(lat)} releases")
    say(f"KV pool {cache_bytes} bytes, memory_peak_bytes {mem_peak}")

    record = {
        "cell": cell_name, "config": c, "mix": mix, "peak": peak,
        "window": (w0, w1), "seconds": w1 - w0, "setup_s": setup_s,
        "clients": list(clients.values()), "counter_log": log,
        "program_counter_log": program_log, "submit_lock": lock_edges,
        "request_stamps": stamps, "blocks_per_row": bpr,
        "monotonic_offset": time.perf_counter() - time.monotonic(),
        "n_slots": s["slots"], "kv_block": s["kv_block"],
        "chunk_tokens": s["chunk_tokens"], "trace": None,
    }
    if trace:
        import trace_reduce
        t = time.perf_counter()
        record["trace"] = trace_reduce.reduce_dir(TRACE_DIR, (w0, w1))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        say(f"trace reduced in {time.perf_counter() - t:.3f} s; share of "
            f"op time with an op_name path "
            f"{record['trace']['scope_mapped_share']}")

    st = run_stats(record)
    summarize(st)

    checks, program_checks = check(ref, params, c, mix, clients, seed,
                                   limits, control)
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    del params
    gc.collect()

    metrics = {}
    for m in metrics_of(spec, cell_name, trace):
        value = metric_reader(m["name"]).read(record)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    out = {"correct": bool(correct), "attempted": st["attempted"],
           "failed": st["failed"], "metrics": metrics, "device": device}
    if trace and record["trace"] is not None:
        device["busy_s"] = record["trace"]["busy_s"]
        device["window_s"] = record["trace"]["window_s"]
        out["breakdown"] = record["trace"]["breakdown"]
    if control:
        out["program_checks"] = program_checks
    out["checks"] = checks
    return out, record


def run_stats(record: dict) -> dict:
    """Medians, tails, counts and the share meeting the mix's limits,
    over the requests due in the window (the attempted ones)."""
    import metrics_common as mc
    ttft, tpot = mc.ttfts(record), mc.tpots(record)
    lim = record["mix"].get("limits")
    w0, w1 = record["window"]
    attempted = [cl for cl in record["clients"] if w0 <= cl.due < w1]

    def pct(x, q):
        return float(np.percentile(x, q)) if len(x) else None
    return {
        "ttft_p50": pct(ttft, 50), "ttft_p95": pct(ttft, 95),
        "tpot_p50": pct(tpot, 50), "tpot_p95": pct(tpot, 95),
        "n_ttft": len(ttft), "n_tpot": len(tpot),
        "tokens": mc.window_tokens(record),
        "tok_s": mc.window_tokens(record) / record["seconds"],
        "meets_limits": mc.meets_limits(record, lim) if lim else None,
        "backlog_at_end": mc.backlog_at_end(record),
        "setup_s": record["setup_s"],
        "attempted": len(attempted),
        "completed": sum(1 for cl in attempted
                         if cl.done is not None and cl.done < w1),
        "failed": sum(bool(cl.error) for cl in attempted),
    }


def summarize(st: dict) -> None:
    say(f"requests due in the window {st['attempted']}, completed in it "
        f"{st['completed']}, failed {st['failed']}")
    say(f"TTFT p50 {st['ttft_p50']} s, p95 {st['ttft_p95']} s over "
        f"{st['n_ttft']} requests; TPOT p50 {st['tpot_p50']} s, p95 "
        f"{st['tpot_p95']} s over {st['n_tpot']} requests")
    say(f"share meeting the mix's limits {st['meets_limits']}; backlog at "
        f"the window's end {st['backlog_at_end']}; tokens delivered in the "
        f"window {st['tokens']}")


def gap_stats(g: np.ndarray) -> str:
    return (f"mean squared gap {np.mean(g * g):.4e}, mean {g.mean():.4e}, "
            f"widest {g.max():.6f}, not the reference's best "
            f"{np.mean(g > 0):.4f}")


def compare(gaps: np.ndarray, short: int, limits: dict) -> dict:
    """The compared numbers, each beside its limit: the mean squared
    gap over the sampled served tokens, and the count of finished
    requests that returned another number of tokens than asked for."""
    return {
        "mean_sq_logit_gap": {"value": float(np.mean(gaps * gaps)),
                              "limit": limits["mean_sq_logit_gap"]},
        "short_answers": {"value": short, "limit": 0},
    }


def check(ref, params, c, mix, clients, seed, limits, control):
    """Compare a seeded sample of finished requests with the plain
    reference; return the checks that decide ``correct`` and, with
    ``control``, the program's own beside them.

    At each served position the gap is how far the served token's
    reference logit lies below the reference's best (0 where it is the
    best). The widest gap and the mean gap are printed too; on the chip
    they do not separate bf16 serving from the fp8 control by three
    times (see PERF.md).

    With ``control`` the fp8 reference takes the program's place: at
    each position of the same prompts and served tokens the token it
    puts first is scored instead of the served one, and those gaps go
    through the same comparison and decide ``correct``."""
    import traffic as traffic_lib
    finished = [cl for cl in clients.values()
                if cl.done is not None and not cl.error]
    short = sum(len(cl.tokens) != cl.max_new for cl in finished)
    if not finished:
        none = {"finished": {"value": 1, "limit": 0}}
        return none, (none if control else None)
    longest = max(finished, key=lambda cl: (len(cl.tokens), cl.prompt_len))
    rest = [cl for cl in finished if cl is not longest]
    rng = np.random.default_rng([int(seed), 11])
    pick = [longest] + [rest[i] for i in rng.permutation(len(rest))[
        :SAMPLE - 1]]
    pad = -(-(traffic_lib.max_prompt_len(mix) + traffic_lib.max_new_cap(mix)
              + 64) // 256) * 256
    t = time.perf_counter()
    gaps, cgaps = [], []
    for cl in pick:
        served = np.asarray(cl.tokens, np.int32)
        gaps.append(ref.served_gaps(params, c, cl.prompt, served, pad))
        if control:
            cgaps.append(ref.served_gaps(params, c, cl.prompt, served, pad,
                                         quant="fp8"))
    g = np.concatenate(gaps)
    say(f"reference over {len(pick)} requests, {len(g)} served tokens, in "
        f"{time.perf_counter() - t:.3f} s: {gap_stats(g)}")
    program = compare(g, short, limits)
    if not control:
        return program, None
    cg = np.concatenate(cgaps)
    say(f"control (fp8 reference in the program's place): {gap_stats(cg)}")
    for k, v in program.items():
        say(f"program check {k} {v['value']} limit {v['limit']}")
    return compare(cg, short, limits), program
