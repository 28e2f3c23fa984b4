"""The DeepSeek-V3 family's benchmark files: a CPU rehearsal of the
``moonlight-16b-a3b-ep8pp2.doc_batch`` cell at a small size (the real
front end, SLO layer and scheduler, kernels in interpret mode) with a
planted fault and the fp8 control each seen to fail the comparison,
the family module's operation and byte counts by hand, and the new
per-layer readers."""

import json
import time

import numpy as np
import pytest

import bench_paths
import flops
import metrics_common as mc
from loader import family

CELL = "moonlight-16b-a3b-ep8pp2.doc_batch"
SEED = 2**33 + 5          # wider than 32 bits, as the driver's are
MOON = json.loads((bench_paths.BENCH / "configs"
                   / "moonlight-16b-a3b-ep8pp2.json").read_text())
FAM = family(MOON)
# On this seed (CPU, the cell's bf16 weights and compute) the sound run
# reads a mean squared gap of 1.6e-4 per served token: at this width an
# expert flipped by a routing near-tie moves a position's logits by up
# to ~0.2. The whole forward in fp8 (the control) on the same sample
# reads 2.9e-3. The limit lies between them, 4x from each.
LIMIT = 7e-4


def smoke_cell():
    c = dict(MOON)
    c.update(num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=4, head_dim=16,
             kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
             num_experts_per_tok=3, router_experts=16, n_routed_experts=8,
             expert_offset=4, vocab_size=512)
    mix = json.loads((bench_paths.BENCH / "traffic" / "doc_batch.json")
                     .read_text())
    mix["prompt"].update(min=8, max=60)
    mix["output"].update(median=24, min=16, max=32)
    mix["arrivals"]["count"] = 12
    mix["serve"].update(slots=4, chunk_tokens=16, kv_blocks=64,
                        max_inflight=8)
    return {"config": c, "mix": mix, "limits": {"mean_sq_logit_gap": LIMIT}}


def run(trace=False, **kw):
    import harness
    return harness.run_cell(CELL, SEED, 4.0, trace,
                            t_start=time.perf_counter(),
                            rehearsal=smoke_cell(), **kw)


@pytest.fixture(scope="module")
def traced_control_run(tmp_path_factory):
    """A traced run whose trace reduction is stood in for (it wants a
    TPU's device plane): every op under both scopes, a ``gmm`` kernel
    time. The fp8 control is scored on the run's sample beside the
    program."""
    import harness
    import trace_reduce

    def reduce_dir(trace_dir, window):
        return {"busy_s": 2.0, "window_s": window[1] - window[0],
                "kernels": {"gmm": 0.5, "paged_attention": 0.25,
                            "flash_prefill": 0.25},
                "scope_busy_s": {"serve.moe": 0.8, "serve.mla": 0.6},
                "scope_mapped_share": None,
                "breakdown": {"device_ops": [], "idle_gaps": [],
                              "idle_by_span": []}}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(trace_reduce, "reduce_dir", reduce_dir)
        mp.setattr(harness, "TRACE_DIR",
                   tmp_path_factory.mktemp("bench_out") / "trace")
        return run(trace=True, control=True)


def test_the_program_passes_and_the_control_fails(traced_control_run):
    out, rec = traced_control_run
    prog = out["program_checks"]["mean_sq_logit_gap"]["value"]
    ctrl = out["checks"]["mean_sq_logit_gap"]["value"]
    assert out["failed"] == 0 and out["attempted"] == 12
    assert all(v["value"] <= v["limit"]
               for v in out["program_checks"].values())
    assert out["correct"] is False
    assert prog <= LIMIT < ctrl


def test_the_traced_run_reports_the_new_readings(traced_control_run):
    out, rec = traced_control_run
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["moe_device_share.batch"] == pytest.approx(40.0)
    assert m["mla_device_share.batch"] == pytest.approx(30.0)
    # 8 of the router's 16 experts held: about half the entries
    assert 25 < m["expert_local_share.batch"] < 75
    for name in ("gmm_roofline.batch", "prefill_row_share.batch",
                 "decode_visited_share.batch", "slot_occupancy.batch",
                 "mfu.batch", "paged_attention_roofline.batch",
                 "flash_prefill_roofline.batch"):
        assert 0 < m[name] <= 100, name
    k = rec["program_counter_log"][-1][1]
    assert {"moe_routed", "moe_local"} <= set(k)


def test_a_bias_the_router_ignores_is_not_correct(monkeypatch):
    """The program's router chooses by the sigmoid scores alone, with
    ``e_score_correction_bias`` dropped."""
    from repro.models import moe
    orig = moe.route

    def no_bias(p, x, cfg):
        return orig(dict(p, e_score_correction_bias=0.0
                         * p["e_score_correction_bias"]), x, cfg)
    monkeypatch.setattr(moe, "route", no_bias)
    out, _ = run()
    assert out["correct"] is False
    assert out["checks"]["mean_sq_logit_gap"]["value"] > LIMIT


# ---------------- operation and byte counts, by hand ------------------

def test_moonlight_sizes():
    s = FAM.sizes(MOON)
    attn = (2048 * 16 * 192 + 2048 * 576 + 16 * 128 * 512
            + 16 * 512 * 128 + 16 * 128 * 2048)
    expert = 3 * 2048 * 1408
    # the router, both shared experts, and 6 x 8/64 routed experts
    moe = 2048 * 64 + 2 * expert + 0.75 * expert
    assert s["token_matmul_params"] == 14 * attn + 3 * 2048 * 11264 \
        + 13 * moe
    assert s["kv_bytes_per_token"] == 14 * 576 * 2 == 16128
    assert s["vocab_padded"] == 20480 and s["moe_layers"] == 13


def test_decode_and_prefill_flops_by_hand():
    s = FAM.sizes(MOON)
    tok = 2 * s["token_matmul_params"]
    head = 2 * 2048 * 20480
    pair = 2 * 16 * (576 + 512) * 14
    assert FAM.decode_flops(MOON, 100) == tok + pair * 100 + head
    assert FAM.prefill_flops(MOON, 2, 3) == 3 * tok + pair * 12 + head


def test_latent_kernel_work_by_hand():
    """Each latent row read once (576 values, not the 640 lanes the
    pool stores), absorbed scores and weighted sums of all 16 heads."""
    qo = 16 * (576 + 512) * 2 * 14
    fl, by = FAM.paged_attention_work(MOON, 1000)
    assert fl == 2 * 16 * (576 + 512) * 14 * 1000
    assert by == 1000 * 16128 + qo
    fl, by = FAM.flash_prefill_work(MOON, 64, 64)
    assert fl == 2 * 16 * (576 + 512) * 14 * flops.attn_pairs(64, 64)
    assert by == 128 * 16128 + 64 * qo


def test_gmm_work_by_hand():
    """96 entries over 13 layer calls: three products per entry, every
    held expert's three matrices read once per call, each entry's rows
    in and out once per product."""
    fl, by = FAM.gmm_work(MOON, 96, 13)
    assert fl == 2 * 3 * 2048 * 1408 * 96
    assert by == 13 * 8 * 3 * 2048 * 1408 * 2 + 96 * 3 * (2048 + 1408) * 2


# ---------------- the new readers, on made-up records -------------------

def _record(counts, kernels=None, trace=True):
    """Counters logged before and inside a window (0, 10)."""
    before = {k: v[0] for k, v in counts.items()}
    after = {k: v[1] for k, v in counts.items()}
    tr = ({"busy_s": 8.0, "kernels": kernels or {}, "scope_busy_s": {}}
          if trace else None)
    return {"config": MOON, "window": (0.0, 10.0), "trace": tr,
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "program_counter_log": [(-1.0, before), (9.0, after)]}


def test_expert_local_share_by_hand():
    import harness
    read = harness.metric_reader("expert_local_share.batch").read
    rec = _record({"moe_routed": (2**32 - 100, 700),
                   "moe_local": (10, 110)})
    assert read(rec) == pytest.approx(100.0 * 100 / 800)  # across a wrap
    assert read(_record({"chunk_iters": (0, 3)})) is None


def test_gmm_roofline_by_hand():
    import harness
    read = harness.metric_reader("gmm_roofline.batch").read
    counts = {"moe_local": (0, 9600), "chunk_iters": (5, 6),
              "decode_iters": (10, 109)}
    fl, by = FAM.gmm_work(MOON, 9600, 100 * 13)
    secs = 2 * by / 819e9
    share = read(_record(counts, {"gmm": secs}))
    assert share == pytest.approx(50.0)
    assert read(_record(counts, {})) is None              # no kernel time
    assert read(_record({"chunk_iters": (0, 1)}, {"gmm": 1.0})) is None
    assert read(_record(counts, trace=False)) is None


def test_the_new_readers_are_silent_on_a_dense_record():
    """A dense configuration's run (or a program without the counters)
    has nothing for them to read."""
    import harness
    dense = json.loads((bench_paths.BENCH / "configs" / "olmo-1b.json")
                       .read_text())
    rec = _record({"chunk_iters": (0, 2), "decode_iters": (0, 9)},
                  {"paged_attention": 1.0})
    rec["config"] = dense
    for name in ("expert_local_share.batch", "gmm_roofline.batch",
                 "moe_device_share.batch", "mla_device_share.batch"):
        assert harness.metric_reader(name).read(rec) is None, name
    assert mc.scope_share(rec, "serve.moe") is None
