"""Spans, scopes and lifetime counters of the serving path.

Host spans (``serve.<what>``, ``jax.profiler.TraceAnnotation``) land on
the profiler's clock; the step program names its two branches with
``jax.named_scope``; the chunk counters in the slot pool and the SLO
layer's submit-lock and per-request stamps are never reset.
"""

import pathlib
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model_zoo
from repro.serve import scheduler as sched_lib
from repro.serve import slo as slo_lib

KEY = jax.random.PRNGKey(5)
PROMPT_LEN, CHUNK, BLOCK = 48, 16, 8


@pytest.fixture(scope="module")
def smollm():
    cfg = get_config("smollm-135m", smoke=True)
    return cfg, model_zoo.init_params(cfg, KEY)


def _sched(model, n_slots, **kw):
    cfg, params = model
    return sched_lib.DecodeScheduler(
        params, cfg, n_slots=n_slots, prompt_len=PROMPT_LEN, max_new_cap=8,
        eos_id=-1, kv="paged", kv_block=BLOCK, prefill="chunked",
        chunk_tokens=CHUNK, **kw)


def _prompt(cfg, length, seed=0):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed),
                                         (1, length), 2, cfg.vocab))


# ----------------------- in-graph chunk counters ---------------------------

@pytest.mark.parametrize("lens, n_slots, want", [
    # one row: ceil(33 / 16) = 3 chunk iterations of 1 row each
    ([33], 1, (3, 3)),
    # admitted together: both prefill at iterations 1-2, the longer
    # alone at 3
    ([20, 40], 2, (3, 5)),
    # the third waits for a slot, which frees after the second's
    # prefill ended (max_new 4), so its 2 chunks run alone
    ([20, 40, 17], 2, (5, 7)),
])
def test_chunk_counters_are_exact(smollm, lens, n_slots, want):
    cfg, _ = smollm
    sched = _sched(smollm, n_slots)
    for i, n in enumerate(lens):
        sched.submit(_prompt(cfg, n, i), max_new=4)
    assert len(sched.run_until_drained()) == len(lens)
    c = sched.counters()
    assert (c["chunk_iters"], c["chunk_rows"]) == want


def test_counters_survive_reset_stats_and_a_new_run(smollm):
    cfg, _ = smollm
    sched = _sched(smollm, 2)
    sched.submit(_prompt(cfg, 33), max_new=4)
    sched.run_until_drained()
    first = sched.counters()
    assert _chunk(first) == {"chunk_iters": 3, "chunk_rows": 3}
    sched.reset_stats()
    assert sched.total_steps == 0 and int(sched.pool.steps) == 0
    assert sched.counters() == first
    # a submit onto the drained scheduler resets the run counters
    # itself; the lifetime ones carry on from where they were
    sched.submit(_prompt(cfg, 17, 1), max_new=4)
    assert sched.counters() == first
    sched.run_until_drained()
    assert _chunk(sched.counters()) == {"chunk_iters": 5, "chunk_rows": 5}


def _chunk(c):
    return {k: c[k] for k in ("chunk_iters", "chunk_rows")}


# ----------------------- in-graph decode counters --------------------------

def _decode_recount(sched):
    """(decode iterations, Σ live pages) of one one-iteration segment,
    recounted on the host from the pool it leaves: a row decoded if it
    is still running or retired in it, and a running row's ``cur_len``
    grew by one there."""
    p = sched.pool
    active, done = np.asarray(p.active), np.asarray(p.done)
    cur = np.asarray(p.cur_len) - active
    if not (active | done).any():
        return 0, 0
    return 1, int(np.sum(-(-cur // BLOCK)))


def test_decode_counters_match_a_host_recount(smollm):
    cfg, _ = smollm
    sched = _sched(smollm, 2)
    for i, n in enumerate([20, 40, 9]):
        sched.submit(_prompt(cfg, n, i), max_new=4 + i)
    want = np.zeros(2, np.int64)
    while sched.pending:
        sched.step(max_steps=1)
        want += _decode_recount(sched)
        c = sched.counters()
        assert (c["decode_iters"], c["decode_pages"]) == tuple(want)
    # three requests of 4-6 tokens over two slots, 2-7 pages a row
    assert 6 <= want[0] < want[1]
    # lifetime: reset_stats leaves both, and a new run adds to them
    before = sched.counters()
    sched.reset_stats()
    assert sched.counters() == before
    sched.submit(_prompt(cfg, 17, 3), max_new=4)
    sched.run_until_drained()
    after = sched.counters()
    assert after["decode_iters"] > before["decode_iters"]
    assert after["decode_pages"] > before["decode_pages"]


def test_decode_pages_zero_for_a_dense_cache(smollm):
    cfg, params = smollm
    sched = sched_lib.DecodeScheduler(
        params, cfg, n_slots=2, prompt_len=PROMPT_LEN, max_new_cap=8,
        eos_id=-1, kv="dense", prefill="chunked", chunk_tokens=CHUNK)
    sched.submit(_prompt(cfg, 20), max_new=4)
    sched.run_until_drained()
    c = sched.counters()
    assert c["decode_iters"] == 4 and c["decode_pages"] == 0


# ----------------------- SLO layer: lock wait and stamps -------------------

def test_submit_lock_wait_grows_behind_a_held_lock(smollm):
    cfg, _ = smollm
    slo = slo_lib.SLOScheduler(_sched(smollm, 2))
    slo.submit(_prompt(cfg, 8), max_new=2)
    assert slo.submits == 1 and slo.submit_lock_wait_s < 0.1
    before = slo.submit_lock_wait_s
    with slo._lock:                    # a round holds it over its segment
        t = threading.Thread(target=slo.submit,
                             args=(_prompt(cfg, 8, 1),),
                             kwargs={"max_new": 2})
        t.start()
        time.sleep(0.3)
    t.join(timeout=60)
    assert not t.is_alive() and slo.submits == 2
    assert slo.submit_lock_wait_s - before >= 0.25
    slo.run_until_drained()
    assert slo.submits == 2 and slo.submit_lock_wait_s - before >= 0.25


def test_stamps_ordered_and_kept_across_preemption(smollm):
    """Batch requests fill a pool sized for two; an interactive one
    preempts a resident. Every request's stamps are ordered, and the
    victim keeps the admission stamp of its first residency."""
    cfg, _ = smollm
    sched = _sched(smollm, 4, kv_blocks=14)     # 7 blocks per request
    slo = slo_lib.SLOScheduler(sched, segment_steps=4)
    for b in range(4):
        slo.submit(_prompt(cfg, 40, b), max_new=8, slo_class="batch",
                   request_id=b)
    slo.step()
    first = {s["request_id"]: s for s in slo.request_stamps()}
    resident = [r for r, s in first.items() if s["admitted"] is not None]
    assert sorted(resident) == [0, 1]
    assert all(first[r]["done"] is None for r in first)
    slo.submit(_prompt(cfg, 40, 9), max_new=8, slo_class="interactive",
               request_id=9)
    victims = {e.request_id for e in slo.run_until_drained()
               if e.kind == "preempted"}
    assert victims and victims <= set(resident)
    stamps = {s["request_id"]: s for s in slo.request_stamps()}
    assert sorted(stamps) == [0, 1, 2, 3, 9]
    for s in stamps.values():
        assert (s["arrival"] <= s["admitted"] <= s["first_token"]
                <= s["done"])
    for r in victims:
        assert stamps[r]["admitted"] == first[r]["admitted"]


def test_finished_stamps_are_a_bounded_record(smollm, monkeypatch):
    cfg, _ = smollm
    monkeypatch.setattr(slo_lib.SLOScheduler, "STAMP_HISTORY", 2)
    slo = slo_lib.SLOScheduler(_sched(smollm, 2))
    for b in range(3):
        slo.submit(_prompt(cfg, 8, b), max_new=2, request_id=b)
    slo.run_until_drained()
    assert [s["request_id"] for s in slo.request_stamps()] == [1, 2]


# ----------------------- scopes and spans on the profiler ------------------

def test_step_program_names_its_branches(smollm):
    sched = _sched(smollm, 2)
    text = sched._step_fn.lower(
        sched.params, None, sched.pool, np.int32(3),
        np.int32(4)).compile().as_text()
    for scope in ("serve.prefill_chunk", "serve.decode"):
        assert f"/{scope}/" in text


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    [f] = pathlib.Path(trace_dir).glob("**/*.xplane.pb")
    spans = []
    for plane in ProfileData.from_file(str(f)).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns, i)
                          for e in line.events
                          if e.name.startswith("serve.")]
    return spans


def test_round_spans_hold_admit_segment_harvest(smollm, tmp_path):
    cfg, _ = smollm
    sched = _sched(smollm, 2)
    sched.warmup()                               # compile off the trace
    slo = slo_lib.SLOScheduler(sched, segment_steps=4)
    with jax.profiler.trace(str(tmp_path)):
        slo.submit(_prompt(cfg, 20), max_new=4)
        slo.run_until_drained()
    spans = _host_spans(tmp_path)
    rounds = [s for s in spans if s[0] == "serve.round"]
    assert rounds and any(s[0] == "serve.submit" for s in spans)
    for inner in ("serve.admit", "serve.segment", "serve.harvest",
                  "serve.observe", "serve.dispatch"):
        assert any(
            r[1] <= s[1] and s[2] <= r[2] and s[3] == r[3]
            for s in spans if s[0] == inner for r in rounds), inner
