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
