"""CPU rehearsal of a whole run: the real front end, SLO layer and
scheduler with a cell's traffic, at the smoke size, kernels in
interpret mode. Also the two faults a served cell can have, planted
underneath, and the control, each seen to fail the comparison."""

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

import bench_paths

SEED = 2**33 + 5          # wider than 32 bits, as the driver's are
# The smoke model's logits have a std of about 0.16 (0.9 at olmo-1b's
# width), so its gaps are far smaller than the cell's. On this seed
# (CPU) the sound run at 2 req/s reads a mean squared gap of 1.4e-7
# per served token, the one at 3 req/s 8.7e-8, and the fp8 control on
# the latter's sample 2.5e-6; the limit lies between them.
LIMIT = 8e-7


def smoke_cell(rate=10.0, ramp=0.5):
    c = json.loads((bench_paths.BENCH / "configs" / "olmo-1b.json")
                   .read_text())
    c.update(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=4, head_dim=16, intermediate_size=128,
             vocab_size=512)
    mix = json.loads((bench_paths.BENCH / "traffic" / "chat.json")
                     .read_text())
    mix["prompt"].update(median=24, min=8, max=60)
    mix["output"].update(median=24, min=16, max=32)
    mix["arrivals"]["rate_per_s"] = rate
    mix["ramp_s"] = ramp
    mix["serve"].update(slots=4, chunk_tokens=16, kv_blocks=64,
                        max_inflight=8)
    return {"config": c, "mix": mix, "limits": {"mean_sq_logit_gap": LIMIT}}


def run(reh, **kw):
    import harness
    return harness.run_cell("olmo-1b.chat", SEED, 4.0, False,
                            t_start=time.perf_counter(), rehearsal=reh, **kw)


@pytest.fixture(scope="module")
def sound_run():
    """A slow arrival rate, so the scheduler drains and resets its
    counters inside the run; the control scored on the same sample."""
    from repro.serve import scheduler
    calls = []
    orig = scheduler.DecodeScheduler.reset_stats

    def counted(self):
        calls.append(1)
        return orig(self)
    scheduler.DecodeScheduler.reset_stats = counted
    try:
        out, rec = run(smoke_cell(rate=2.0))
    finally:
        scheduler.DecodeScheduler.reset_stats = orig
    return out, rec, len(calls)


def test_result_line_keys_and_checks_last(sound_run):
    out, _, _ = sound_run
    keys = list(out)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics",
                        "device"]
    assert keys[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"output_tok_s.serve", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert set(out["checks"]) == {"mean_sq_logit_gap", "short_answers"}


def test_event_timestamps_and_token_counts(sound_run):
    _, rec, _ = sound_run
    done = [cl for cl in rec["clients"] if cl.done is not None]
    assert len(done) >= 2
    for cl in rec["clients"]:
        if cl.released is None:
            continue
        assert cl.released >= cl.due - 1e-3
        times = [t for t, _ in cl.events]
        assert times == sorted(times) and all(t >= cl.released
                                              for t in times)
        assert sum(n for _, n in cl.events) == len(cl.tokens)
    for cl in done:
        assert len(cl.tokens) == cl.max_new
        assert cl.done >= cl.events[-1][0]


def test_counters_accumulate_across_resets(sound_run):
    _, rec, resets = sound_run
    assert resets >= 2       # warm-up's own and the first submission's
    it = [int(c[0]) for _, c in rec["counter_log"]]
    assert it == sorted(it) and it[-1] > 0


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """A traced run: the profiler records, and its ``.xplane.pb`` is
    read; the rest of the reduction (which wants a TPU's device plane)
    is stood in for. The program's ``counters()`` carries one key more
    than it has, as a later program's might: ``probe``, the number of
    times it was read."""
    import harness
    import trace_reduce
    from repro.serve import scheduler
    seen = {}
    reads = [0]
    orig = scheduler.DecodeScheduler.counters

    def counters(self):
        reads[0] += 1
        return dict(orig(self), probe=reads[0])

    def reduce_dir(trace_dir, window):
        files = sorted(trace_dir.glob("**/*.xplane.pb"))
        seen["ev"] = trace_reduce.read_xspace(files[-1].read_bytes())
        return {"busy_s": 1.0, "window_s": window[1] - window[0],
                "kernels": {}, "scope_busy_s": {},
                "scope_mapped_share": None,
                "breakdown": {"device_ops": [], "idle_gaps": [],
                              "idle_by_span": []}}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(scheduler.DecodeScheduler, "counters", counters)
        mp.setattr(trace_reduce, "reduce_dir", reduce_dir)
        mp.setattr(harness, "TRACE_DIR",
                   tmp_path_factory.mktemp("bench_out") / "trace")
        out, rec = harness.run_cell(
            "olmo-1b.chat", SEED, 4.0, True, t_start=time.perf_counter(),
            rehearsal=smoke_cell())
    return out, rec, seen["ev"]


def test_a_traced_run_logs_the_program_counters_each_round(traced_run):
    _, rec, _ = traced_run
    log = rec["program_counter_log"]
    assert len(log) == len(rec["counter_log"]) + 1    # and one at start
    assert all(set(c) == {"chunk_iters", "chunk_rows", "decode_iters",
                          "decode_pages", "probe"} for _, c in log)
    assert [c["probe"] for _, c in log] == list(range(1, len(log) + 1))
    assert rec["blocks_per_row"] == -(-(60 + 32) // 16)


def test_an_untraced_run_logs_no_program_reading(sound_run):
    _, rec, _ = sound_run
    assert rec["program_counter_log"] is None
    assert rec["submit_lock"] is None and rec["request_stamps"] is None


def test_a_new_counter_reaches_the_window_delta(traced_run):
    """``probe`` is in no bench file: its delta over the window is the
    number of rounds logged inside the window."""
    import metrics_common as mc
    _, rec, _ = traced_run
    w0, w1 = rec["window"]
    times = [t for t, _ in rec["program_counter_log"]]
    inside = sum(1 for t in times if w0 < t <= w1)
    assert inside > 0
    assert mc.program_counter_delta(rec, "probe") == inside
    assert mc.program_counter_delta(rec, "no_such_counter") is None


def test_a_traced_run_reports_the_program_readings(traced_run):
    out, rec, ev = traced_run
    assert out["correct"] is True
    for name in ("prefill_row_share.serve", "decode_visited_share.serve",
                 "submit_blocked_share.serve", "queue_wait_p95"):
        assert name in out["metrics"], name
    assert 0 < out["metrics"]["prefill_row_share.serve"]["value"] <= 100
    assert 0 < out["metrics"]["decode_visited_share.serve"]["value"] <= 100
    assert 0 <= out["metrics"]["submit_blocked_share.serve"]["value"]
    lock = rec["submit_lock"]
    assert lock[1]["submits"] > lock[0]["submits"]
    rids = {cl.rid for cl in rec["clients"]}
    assert rec["request_stamps"] and all(
        s["request_id"] in rids for s in rec["request_stamps"])
    # the program's own host spans reach the reduction's input
    names = {p[0] for p in ev["program"]}
    assert {"serve.round", "serve.submit"} <= names


def test_counted_slo_folds_counters_before_each_reset():
    """Two runs on a scheduler that drains between them: the inner
    counters restart, the harness's fold keeps both runs' iterations."""
    import dataclasses

    import harness
    import weights
    from loader import family
    from repro.models import model_zoo
    from repro.serve import scheduler, slo

    c = smoke_cell()["config"]
    cfg = family(c).program_config(c)
    params = weights.build(model_zoo.abstract_params(cfg), c, SEED)
    sched = scheduler.DecodeScheduler(
        params, dataclasses.replace(cfg, attn_impl="xla"), n_slots=2,
        prompt_len=16, max_new_cap=4, eos_id=-1, kv="paged",
        kv_blocks=16, prefill="chunked", chunk_tokens=8)
    counted = harness.counted_slo(slo)(sched, segment_steps=2,
                                       read_slot_steps=True)
    per_run = []
    for r in range(2):
        counted.submit(np.full((1, 12), 3 + r, np.int32), max_new=4,
                       request_id=r)
        counted.run_until_drained()
        per_run.append(sched.total_steps)
    assert min(per_run) > 0
    assert int(counted.counters()[0]) == sum(per_run)
    assert int(counted.counters()[2]) == 2 * 4      # one slot, 4 tokens


def test_control_fails_where_the_program_passes():
    """The plain reference in fp8, in the program's place, on the same
    prompts and served tokens: the token it puts first lies further
    below the reference's best than the limit allows, and the run's own
    comparison reads ``correct`` false, while the program's checks on
    the same sample pass."""
    out, _ = run(smoke_cell(rate=3.0), control=True)
    prog = out["program_checks"]["mean_sq_logit_gap"]["value"]
    ctrl = out["checks"]["mean_sq_logit_gap"]["value"]
    assert out["correct"] is False
    assert list(out)[-1] == "checks"
    assert all(v["value"] <= v["limit"]
               for v in out["program_checks"].values())
    assert prog <= LIMIT < ctrl


def test_a_generator_module_gives_requests_their_own_class(
        tmp_path, monkeypatch):
    """A mix that names a generator module of its own is served as that
    module schedules it, each request in the SLO class it was given."""
    import traffic
    from repro.serve import frontend
    (tmp_path / "two_classes.py").write_text(
        "import traffic\n"
        "def schedule(mix, vocab, seed, seconds):\n"
        "    base = {k: v for k, v in mix.items() if k != 'generator'}\n"
        "    s = traffic.schedule(base, vocab, seed, seconds)\n"
        "    for r in s.requests[1::2]:\n"
        "        r.slo_class = 'batch'\n"
        "    return s\n"
        "def max_prompt_len(mix):\n"
        "    return mix['prompt']['max']\n"
        "def max_new_cap(mix):\n"
        "    return mix['output']['max']\n")
    monkeypatch.setattr(traffic, "MIX_DIR", tmp_path)
    seen = {}
    orig = frontend.StreamingFrontend.stream

    def stream(self, prompt, *, slo_class="batch", request_id=None, **kw):
        seen[request_id] = slo_class
        return orig(self, prompt, slo_class=slo_class,
                    request_id=request_id, **kw)
    monkeypatch.setattr(frontend.StreamingFrontend, "stream", stream)
    reh = smoke_cell()
    reh["mix"]["generator"] = "two_classes"
    out, _ = run(reh)
    assert out["correct"] is True
    assert len(seen) >= 4
    assert all(k == ("batch" if rid % 2 else "interactive")
               for rid, k in seen.items())


def test_an_altered_token_is_not_correct(monkeypatch):
    """Every served token is shifted by one id where it is sampled."""
    from repro.serve import sampling
    orig = sampling.sample_slots

    def shifted(logits, keys, sp):
        return (orig(logits, keys, sp) + 1) % logits.shape[-1]
    monkeypatch.setattr(sampling, "sample_slots", shifted)
    out, _ = run(smoke_cell())
    assert out["correct"] is False
    assert out["checks"]["mean_sq_logit_gap"]["value"] > \
        out["checks"]["mean_sq_logit_gap"]["limit"]


def test_rows_served_into_another_request_are_not_correct(monkeypatch):
    """Each finished request is handed the tokens of the request that
    finished before it (the fault the one-chip bring-up found: rows of
    one request served into another's); the first gets its own
    reversed."""
    from repro.serve import scheduler
    orig = scheduler.DecodeScheduler._harvest
    carry = []

    def shifted(self):
        got = orig(self)
        for g in got:
            own = g.tokens
            g.tokens = carry[-1] if carry else own[::-1].copy()
            carry.append(own)
        return got
    monkeypatch.setattr(scheduler.DecodeScheduler, "_harvest", shifted)
    out, _ = run(smoke_cell(rate=10.0))
    assert out["correct"] is False


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch):
    """The device step hands back the pool it was given: nothing is
    ever served, so no request finishes."""
    from repro.serve import scheduler

    def frozen(self):
        return lambda params, dparams, pool, want, max_steps: pool
    monkeypatch.setattr(scheduler.DecodeScheduler, "_build_step", frozen)
    out, _ = run(smoke_cell())
    assert out["correct"] is False
    assert out["checks"]["finished"]["value"] > 0


def _run_py(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "olmo-1b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_py_exits_nonzero_without_a_tpu():
    r = _run_py(bench_paths.ROOT, {})
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_run_py_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(bench_paths.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(bench_paths.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_py(tmp_path, {})
    assert r.returncode != 0 and r.stdout.strip() == ""
