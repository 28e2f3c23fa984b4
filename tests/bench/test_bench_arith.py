"""Roofline, MFU and peak arithmetic against hand-computed numbers."""

import json

import numpy as np
import pytest

import bench_paths
import flops
import metrics_common as mc
import peaks
from loader import family

OLMO = json.loads((bench_paths.BENCH / "configs" / "olmo-1b.json")
                  .read_text())
DENSE = family(OLMO)
V5E = peaks.PEAKS["TPU v5 lite"]


def test_peaks_by_device_kind_and_unknown_kind_raises():
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def test_olmo_sizes():
    s = DENSE.sizes(OLMO)
    # 4 * 2048 * 2048 attention + 3 * 2048 * 8192 SwiGLU per layer
    assert s["layer_matmul_params"] == 4 * 2048 ** 2 + 3 * 2048 * 8192
    assert s["kv_bytes_per_token"] == 16 * 2 * 16 * 128 * 2 == 131072
    assert s["vocab_padded"] == 50432


def test_decode_and_prefill_flops_by_hand():
    s = DENSE.sizes(OLMO)
    dense = 2 * s["layer_matmul_params"] * 16
    head = 2 * 2048 * 50432
    assert DENSE.decode_flops(OLMO, 100) == dense + 4 * 16 * 128 * 16 * 100 \
        + head
    # 3 prompt tokens from position 2 see 3, 4, 5 keys
    assert flops.attn_pairs(2, 3) == 3 + 4 + 5
    assert DENSE.prefill_flops(OLMO, 2, 3) == 3 * dense \
        + 4 * 16 * 128 * 16 * 12 + head


def test_paged_attention_work_and_memory_bound_share():
    fl, by = DENSE.paged_attention_work(OLMO, 1000)
    assert fl == 4 * 16 * 128 * 1000 * 16
    assert by == 1000 * 131072 + 2 * 16 * 128 * 2 * 16
    # 1000 tokens of 128 KiB at 819 GB/s take 160.1 us; measured 320.2 us
    share, bound = flops.roofline_share(fl, by, 2 * by / 819e9, V5E)
    assert bound == "memory" and share == pytest.approx(50.0)


def test_compute_bound_share():
    share, bound = flops.roofline_share(197e12, 1.0, 4.0, V5E)
    assert bound == "compute" and share == pytest.approx(25.0)


def test_flash_prefill_work():
    fl, by = DENSE.flash_prefill_work(OLMO, 128, 128)
    assert fl == 4 * 16 * 128 * flops.attn_pairs(128, 128) * 16
    assert by == 256 * 131072 + 2 * 128 * 16 * 128 * 2 * 16


class C:
    def __init__(self, due, plen, events, max_new=4):
        self.due, self.prompt_len, self.events = due, plen, events
        self.max_new = max_new


def record(clients, **kw):
    r = {"window": (10.0, 20.0), "seconds": 10.0, "clients": clients,
         "config": OLMO, "peak": V5E, "kv_block": 16, "chunk_tokens": 128,
         "mix": {"serve": {"prefix_cache": True}}, "trace": None,
         "counter_log": [], "n_slots": 4}
    r.update(kw)
    return r


def test_ttft_counts_a_missing_first_token_at_its_wait():
    r = record([C(11.0, 10, [(11.5, 1)]), C(19.0, 10, []),
                C(9.0, 10, [(10.5, 1)])])
    assert list(mc.ttfts(r)) == pytest.approx([0.5, 1.0])


def test_tpot_over_tokens_after_the_first_event():
    r = record([C(11.0, 10, [(12.0, 2), (12.5, 3), (13.0, 2), (25.0, 1)])])
    # 1.0 s from the first to the last in-window event, 5 tokens after it
    assert list(mc.tpots(r)) == pytest.approx([0.2])
    assert mc.window_tokens(r) == 7


def test_mfu_of_one_decode_token_by_hand():
    r = record([C(1.0, 100, [(5.0, 1), (15.0, 1)])],
               trace={"busy_s": 2.0, "window_s": 10.0, "kernels": {}})
    # prefill ran before the window; token 1 is made by feeding token 0
    # at position 100, which attends over 101 positions
    assert mc.model_flops(r) == DENSE.decode_flops(OLMO, 101)
    mfu = bench_paths_read("mfu", r)
    assert mfu == pytest.approx(100 * DENSE.decode_flops(OLMO, 101) / 2.0
                                / 197e12)


def test_model_flops_count_the_prompts_prefilled_in_the_window():
    """A request whose first token came in the window had its whole
    prompt prefilled there; one that got it before the window did not."""
    r = record([C(11.0, 3, [(12.0, 1)]), C(1.0, 50, [(5.0, 1)])],
               trace={"busy_s": 1.0, "window_s": 10.0, "kernels": {}})
    assert mc.model_flops(r) == DENSE.prefill_flops(OLMO, 0, 3)
    assert [cl.prompt_len for cl in mc.prefilled(r)] == [3]


def test_counter_delta_and_occupancy_across_the_window():
    log = [(9.0, np.array([10, 0, 20])), (15.0, np.array([30, 2, 80])),
           (19.0, np.array([50, 4, 140])), (21.0, np.array([60, 5, 170]))]
    # one request prefilled in the window; one released, still waiting
    r = record([C(12.0, 64, [(13.0, 1)]), C(14.0, 500, [])],
               counter_log=log)
    assert list(mc.counter_delta(r)) == [40, 4, 120]
    assert bench_paths_read("slot_occupancy", r) == pytest.approx(75.0)


def test_idle_share_reader():
    r = record([], trace={"busy_s": 7.5, "window_s": 10.0, "kernels": {}})
    assert bench_paths_read("device_idle_share", r) == pytest.approx(
        25.0)


def test_roofline_reader_is_silent_without_kernel_time():
    r = record([C(1.0, 100, [(15.0, 1)])],
               trace={"busy_s": 1.0, "window_s": 10.0, "kernels": {}})
    assert bench_paths_read("paged_attention_roofline", r) is None


@pytest.mark.parametrize("name", ["mfu.serve", "mfu.batch", "mfu"])
def test_a_split_metric_is_read_by_the_file_of_its_base_name(name):
    import harness
    assert harness.metric_reader(name).__file__.endswith("/mfu.py")


def test_a_metric_without_a_reader_raises():
    import harness
    with pytest.raises(FileNotFoundError):
        harness.metric_reader("no_such_metric.serve")


def bench_paths_read(name, r):
    import harness
    return harness.metric_reader(name).read(r)


def counters_log(*rows):
    """Program counter readings (t, {name: value}) as a traced run logs
    them; values are int32, as the device holds them."""
    return [(t, dict(c)) for t, c in rows]


def test_program_counter_delta_across_the_window_and_its_wrap():
    log = counters_log(
        (5.0, {"c": 2**31 - 10}), (9.5, {"c": 2**31 - 4}),
        (15.0, {"c": -2**31 + 2}), (19.0, {"c": -2**31 + 6}),
        (21.0, {"c": -2**31 + 9}))
    r = record([], program_counter_log=log)
    # from 2**31 - 4 to 2**31 + 6 as int32 wrapped: 10
    assert mc.program_counter_delta(r, "c") == 10
    assert mc.program_counter_delta(r, "absent") is None
    assert mc.program_counter_delta(record([]), "c") is None
    late = record([], program_counter_log=counters_log((12.0, {"c": 3})))
    assert mc.program_counter_delta(late, "c") is None


def chunk_log(iters, rows, d_iters, pages):
    return counters_log(
        (9.0, {"chunk_iters": 100, "chunk_rows": 300, "decode_iters": 50,
               "decode_pages": 1000}),
        (20.0, {"chunk_iters": 100 + iters, "chunk_rows": 300 + rows,
                "decode_iters": 50 + d_iters,
                "decode_pages": 1000 + pages}))


def test_prefill_row_share_by_hand():
    # 10 chunk iterations over 4 slots, 6 prefilling rows among them
    r = record([], program_counter_log=chunk_log(10, 6, 0, 0))
    assert bench_paths_read("prefill_row_share.serve", r) == \
        pytest.approx(100 * 6 / 40)


def test_decode_visited_share_by_hand():
    # 20 decode iterations, 4 slots, 8 pages per row: 640 in the grid
    r = record([], program_counter_log=chunk_log(0, 0, 20, 160),
               blocks_per_row=8)
    assert bench_paths_read("decode_visited_share.batch", r) == \
        pytest.approx(25.0)


def test_submit_blocked_share_by_hand():
    r = record([], submit_lock=[
        {"submit_lock_wait_s": 1.5, "submits": 3},
        {"submit_lock_wait_s": 9.0, "submits": 40}])
    assert bench_paths_read("submit_blocked_share.serve", r) == \
        pytest.approx(75.0)


def test_prefill_device_share_by_hand():
    r = record([], trace={"busy_s": 8.0, "window_s": 10.0, "kernels": {},
                          "scope_busy_s": {"serve.prefill_chunk": 6.0,
                                           "serve.decode": 1.5}})
    assert bench_paths_read("prefill_device_share.serve", r) == \
        pytest.approx(75.0)
    assert mc.scope_share(r, "serve.decode") == pytest.approx(18.75)
    assert mc.scope_share(r, "moe") is None


def test_queue_wait_counts_a_request_not_admitted_at_its_wait_so_far():
    """Stamps on the program's clock, 100 s behind the benchmark's: a
    request admitted 2 s after its submission, one not admitted by the
    window's end (20 on the benchmark's clock), and one due before the
    window, left out."""
    cls = [C(11.0, 10, []), C(12.0, 10, []), C(5.0, 10, [])]
    for rid, cl in enumerate(cls):
        cl.rid = rid
    stamps = [
        {"request_id": 0, "arrival": -89.0, "admitted": -87.0},
        {"request_id": 1, "arrival": -88.0, "admitted": None},
        {"request_id": 2, "arrival": -95.0, "admitted": -70.0}]
    r = record(cls, request_stamps=stamps, monotonic_offset=100.0)
    assert bench_paths_read("queue_wait_p95", r) == pytest.approx(
        float(np.percentile([2.0, 8.0], 95)))


@pytest.mark.parametrize("name", [
    "prefill_device_share.serve", "prefill_row_share.batch",
    "decode_visited_share.serve", "submit_blocked_share.serve",
    "queue_wait_p95"])
def test_new_readers_are_silent_without_their_data(name):
    """A run of a program from before the counters, spans and stamps
    existed, or an untraced run: every new reader gives None."""
    r = record([], trace={"busy_s": 1.0, "window_s": 10.0, "kernels": {}},
               program_counter_log=None, submit_lock=None,
               request_stamps=None, blocks_per_row=None)
    assert bench_paths_read(name, r) is None
    assert bench_paths_read(name, record([])) is None


def test_decode_visited_share_needs_the_table_width():
    r = record([], program_counter_log=chunk_log(0, 0, 20, 160),
               blocks_per_row=None)
    assert bench_paths_read("decode_visited_share.serve", r) is None
