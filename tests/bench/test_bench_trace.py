"""The reduction from a profiler trace to busy time and kernel time."""

import pytest

import bench_paths  # noqa: F401
import trace_reduce

# Two chips' "XLA Ops" lines and host spans, times in ns after 1000.
# The measured window (host span bench.window) is [1000, 10000).
# Chip 0: a while op [0, 3000) holding fusion.1 [0, 2000) and the
# paged-attention call [1000, 3000), then the prefill call [5000, 6000):
# busy 3000 ns inside the window, one gap of 2000 ns under the host
# span bench.slo_step. Events carry the op's HLO text, whose operands
# name other ops. Chip 1: one op [0, 2000), 1000 ns inside the window.
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 5 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 9000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = bf16[2] fusion(%flash_prefill.8)" } }
  event_metadata { key: 2 value { id: 2 name: "%paged_attention.9 = bf16[2] custom-call(%p)" } }
  event_metadata { key: 3 value { id: 3 name: "%flash_prefill.8 = bf16[2] custom-call(%q)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_step" } }
  event_metadata { key: 5 value { id: 5 name: "%while.44 = (s32[]) while(%t)" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
}
planes {
  id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 2500000 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 1000000 } }
  lines { id: 2 name: "loop" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 9000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.slo_step" } }
  event_metadata { key: 2 value { id: 2 name: "unrelated" } }
  event_metadata { key: 3 value { id: 3 name: "bench.window" } }
}
"""


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    ev = trace_reduce.extract(ProfileData.from_text_proto(TRACE))
    return ev, trace_reduce.reduce(ev, window_s=99.0)


def test_extract_keeps_ops_lines_and_benchmark_spans(reduced):
    ev, _ = reduced
    assert sorted(ev["devices"]) == ["/device:TPU:0", "/device:TPU:1"]
    assert [trace_reduce.op_name(e[0])
            for e in ev["devices"]["/device:TPU:0"]] == [
        "%while.44", "%fusion.1", "%paged_attention.9", "%flash_prefill.8"]
    assert [h[0] for h in ev["host"]] == ["bench.slo_step", "bench.window"]


def test_busy_is_the_union_clipped_to_the_window_averaged_over_chips(
        reduced):
    _, r = reduced
    # chip 0: [1000, 3000) + [5000, 6000) = 3000 ns; chip 1: 1000 ns
    assert r["busy_s"] == pytest.approx(2000e-9)
    assert r["window_s"] == pytest.approx(9000e-9)


def test_without_a_window_span_the_given_window_counts():
    ev = {"devices": {"/device:TPU:0": [("%a = x", 0, 4000)]}, "host": []}
    r = trace_reduce.reduce(ev, 1e-5)
    assert r["busy_s"] == pytest.approx(4e-6) and r["window_s"] == 1e-5


def test_kernel_time_found_by_its_own_name(reduced):
    _, r = reduced
    # per-chip mean: each kernel ran on chip 0 only; fusion.1, which
    # reads the prefill call's output, is not prefill time
    assert r["kernels"]["paged_attention"] == pytest.approx(1000e-9)
    assert r["kernels"]["flash_prefill"] == pytest.approx(500e-9)


def test_breakdown_ops_and_labelled_gaps(reduced):
    _, r = reduced
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["%fusion.1"] == pytest.approx(500e-9)
    assert "%while.44" not in ops
    assert r["breakdown"]["idle_gaps"] == [
        ["bench.slo_step", pytest.approx(2000e-9)]]


def test_union_merges_touching_and_nested():
    assert trace_reduce.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [
        (0, 4), (5, 6)]


def test_a_trace_without_device_ops_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce({"devices": {}, "host": []}, 1.0)


def old_prefix_table_sums(ev):
    """Kernel time as the reduction summed it before every custom call
    was found by its stem: a fixed table of HLO name prefixes."""
    table = {"paged_attention": "%paged_attention",
             "flash_prefill": "%flash_prefill"}
    lo, hi = [(s, s + d) for n, s, d in ev["host"]
              if n == trace_reduce.WINDOW_SPAN][0]
    devs = {k: trace_reduce.clip(v, lo, hi) for k, v in ev["devices"].items()}
    out = {}
    for k, prefix in table.items():
        out[k] = sum(d for events in devs.values() for name, _, d in events
                     if trace_reduce.op_name(name).startswith(prefix)) \
            / len(devs) / 1e9
    return out


def test_known_kernels_sum_as_the_old_prefix_table(reduced):
    ev, r = reduced
    for k, v in old_prefix_table_sums(ev).items():
        assert r["kernels"][k] == v


# One chip, times in ns after 1000; the window is [1000, 11000). XLA
# Ops: a while [0, 6000) holding fusion.2 [0, 1000), the prefill call
# [1000, 3000), a custom call no table lists, grouped_matmul.3
# [3000, 4000) under a new "moe" scope, the decode call [4000, 5000)
# and copy.5 [5000, 6000), which has no op_name; then fusion.4
# [8000, 8500) and convert.6 [8500, 9000), which has none either. Busy
# 7000 ns, idle [6000, 8000) and [9000, 10000). Scope paths ride in the
# event metadata's tf_op stat, as a string or as a reference to a stat
# name, some with the ":<type>" the trace appends. Host: the
# scheduler's thread runs serve.round [5500, 9800) with serve.harvest
# [6000, 7000) and serve.dispatch [7500, 8500) inside, under
# bench.slo_step; the event loop's thread runs serve.submit
# [6500, 9500) (waiting on the round's lock) and serve.fanout
# [9700, 10000).
PFX = "jit(step)/while/body/serve.prefill_chunk/cond/branch_1_fun"
DEC = "jit(step)/while/body/serve.decode"
SCOPED = f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 6000000 }}
    events {{ metadata_id: 2 offset_ps: 0 duration_ps: 1000000 }}
    events {{ metadata_id: 3 offset_ps: 1000000 duration_ps: 2000000 }}
    events {{ metadata_id: 4 offset_ps: 3000000 duration_ps: 1000000 }}
    events {{ metadata_id: 5 offset_ps: 4000000 duration_ps: 1000000 }}
    events {{ metadata_id: 6 offset_ps: 5000000 duration_ps: 1000000 }}
    events {{ metadata_id: 7 offset_ps: 8000000 duration_ps: 500000 }}
    events {{ metadata_id: 8 offset_ps: 8500000 duration_ps: 500000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%while.7 = (s32[]) while(%t)"
    stats {{ metadata_id: 1 str_value: "jit(step)/while" }} }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%fusion.2 = bf16[2] fusion(%a)"
    stats {{ metadata_id: 1 str_value: "{PFX}/add" }} }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "%flash_prefill.8 = bf16[2] custom-call(%q)"
    stats {{ metadata_id: 2 int64_value: 3 }}
    stats {{ metadata_id: 1 str_value: "{PFX}/pallas_call:" }} }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "%grouped_matmul.3 = bf16[2] custom-call(%x)"
    stats {{ metadata_id: 1 str_value: "{DEC}/moe/pallas_call" }} }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "%paged_attention.9 = bf16[2] custom-call(%p)"
    stats {{ metadata_id: 1 str_value: "{DEC}/pallas_call" }} }} }}
  event_metadata {{ key: 6 value {{ id: 6 name: "%copy.5 = bf16[2] copy(%grouped_matmul.3)" }} }}
  event_metadata {{ key: 7 value {{ id: 7 name: "%fusion.4 = bf16[2] fusion(%b)"
    stats {{ metadata_id: 1 ref_value: 9 }} }} }}
  event_metadata {{ key: 8 value {{ id: 8 name: "%convert.6 = bf16[2] convert(%c)" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
  stat_metadata {{ key: 2 value {{ id: 2 name: "flops" }} }}
  stat_metadata {{ key: 9 value {{ id: 9 name: "{DEC}/mul" }} }}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "scheduler" timestamp_ns: 1000
    events {{ metadata_id: 1 offset_ps: 5500000 duration_ps: 4500000 }}
    events {{ metadata_id: 2 offset_ps: 5500000 duration_ps: 4300000 }}
    events {{ metadata_id: 3 offset_ps: 6000000 duration_ps: 1000000 }}
    events {{ metadata_id: 4 offset_ps: 7500000 duration_ps: 1000000 }} }}
  lines {{ id: 2 name: "loop" timestamp_ns: 1000
    events {{ metadata_id: 5 offset_ps: 0 duration_ps: 10000000 }}
    events {{ metadata_id: 6 offset_ps: 6500000 duration_ps: 3000000 }}
    events {{ metadata_id: 7 offset_ps: 9700000 duration_ps: 300000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.slo_step" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "serve.round" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "serve.harvest" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "serve.dispatch" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "bench.window" }} }}
  event_metadata {{ key: 6 value {{ id: 6 name: "serve.submit" }} }}
  event_metadata {{ key: 7 value {{ id: 7 name: "serve.fanout" }} }}
}}
"""


@pytest.fixture(scope="module")
def scoped():
    from jax.profiler import ProfileData
    ev = trace_reduce.read_xspace(
        ProfileData.text_proto_to_serialized_xspace(SCOPED))
    return ev, trace_reduce.reduce(ev, window_s=99.0)


def test_op_paths_read_from_the_event_metadata(scoped):
    ev, _ = scoped
    paths = ev["op_paths"]
    assert paths["%flash_prefill.8 = bf16[2] custom-call(%q)"] == \
        f"{PFX}/pallas_call:"
    assert paths["%fusion.4 = bf16[2] fusion(%b)"] == f"{DEC}/mul"
    assert not any(k.startswith("%copy.5") for k in paths)
    assert len(paths) == 6


def test_a_custom_call_no_table_lists_is_found_by_its_stem(scoped):
    ev, r = scoped
    assert r["kernels"] == pytest.approx({
        "flash_prefill": 2000e-9, "grouped_matmul": 1000e-9,
        "paged_attention": 1000e-9})
    for k, v in old_prefix_table_sums(ev).items():
        assert r["kernels"][k] == v


@pytest.mark.parametrize("event,stem", [
    ("%paged_attention.9 = bf16[2] custom-call(%p)", "paged_attention"),
    ("%grouped_matmul.3.12 = (bf16[2], f32[]) custom-call(%x)",
     "grouped_matmul"),
    ("%custom-call.5 = f32[4] custom-call(%y), custom_call_target=\"x\"",
     "custom-call"),
    ("%fusion.1 = bf16[2] fusion(%flash_prefill.8)", None),
    ("%copy.3 = bf16[2] copy(%custom-call.5)", None),
])
def test_kernel_stem(event, stem):
    assert trace_reduce.kernel_of(event) == stem


def test_scope_time_and_mapped_share(scoped):
    _, r = scoped
    s = r["scope_busy_s"]
    assert s["serve.prefill_chunk"] == pytest.approx(3000e-9)
    assert s["serve.decode"] == pytest.approx(2500e-9)
    assert s["moe"] == pytest.approx(1000e-9)         # a new scope
    assert s["jit(step)"] == pytest.approx(5500e-9)   # the while left out
    assert s["pallas_call"] == pytest.approx(4000e-9)
    assert "pallas_call:" not in s
    # 5500 of the 7000 ns of operations have a path; copy.5 and
    # convert.6 have none
    assert r["scope_mapped_share"] == pytest.approx(100 * 5.5 / 7)


def test_idle_by_span_goes_to_the_innermost_span_of_the_first_thread(
        scoped):
    _, r = scoped
    assert r["busy_s"] == pytest.approx(7000e-9)
    by = dict(r["breakdown"]["idle_by_span"])
    assert by == pytest.approx({"serve.harvest": 1000e-9,
                                "serve.round": 1300e-9,
                                "serve.dispatch": 500e-9,
                                "serve.fanout": 200e-9})
    assert sum(by.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_idle_by_span_follows_a_round_across_threads():
    """Rounds run on whichever worker thread is free: idle goes to the
    thread whose outermost covering span began first, here a submission
    the second round waits behind, and to the round's own spans once the
    submission is done. Device busy [0, 50) of a [0, 400) window."""
    ev = {"devices": {"/device:TPU:0": [("%a = x", 0, 50)]},
          "host": [(trace_reduce.WINDOW_SPAN, 0, 400)],
          "program": [("serve.round", 0, 100, "w1"),
                      ("serve.round", 200, 200, "w2"),
                      ("serve.segment", 250, 150, "w2"),
                      ("serve.submit", 150, 150, "loop")]}
    by = dict(trace_reduce.reduce(ev, 1.0)["breakdown"]["idle_by_span"])
    assert by == pytest.approx({"serve.round": 50e-9,
                                trace_reduce.NO_SPAN: 50e-9,
                                "serve.submit": 150e-9,
                                "serve.segment": 100e-9})


def test_idle_by_span_sums_to_the_idle_of_every_chip(reduced):
    """Two chips, no program span: each chip's idle, window edges
    included, averaged over the chips."""
    _, r = reduced
    by = dict(r["breakdown"]["idle_by_span"])
    assert list(by) == [trace_reduce.NO_SPAN]
    assert by[trace_reduce.NO_SPAN] == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_idle_gaps_labels_ignore_program_spans(scoped):
    ev, r = scoped
    bare = dict(ev, program=[])
    assert r["breakdown"]["idle_gaps"] == \
        trace_reduce.reduce(bare, 99.0)["breakdown"]["idle_gaps"] == [
            ["bench.slo_step", pytest.approx(2000e-9)]]
    assert r["breakdown"]["device_ops"] == \
        trace_reduce.reduce(bare, 99.0)["breakdown"]["device_ops"]


def test_breakdown_lists_fold_what_does_not_fit():
    d = {f"s{i}": float(i) * 1e9 for i in range(1, 13)}
    out = trace_reduce.top_entries(d)
    assert len(out) == trace_reduce.MAX_ENTRIES
    assert out[0] == ["s12", 12.0] and out[-1] == ["other", 1 + 2 + 3.0]
    assert sum(v for _, v in out) == pytest.approx(78.0)
