"""BENCHMARK.json: every cell resolves to its files by name, and the
file keeps the contract's shape."""

import json
import re

import pytest

import bench_paths
from loader import family

ROOT = bench_paths.ROOT
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    for p in SPEC["paths"]:
        assert (ROOT / p).is_dir()


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    import harness
    import traffic
    w = harness.cell_of(SPEC, cell)
    assert w["chips"] == 1 and len(w["why"]) <= 200
    entry = harness.config_entry(SPEC, w["config"])
    c = harness.load_config(SPEC, w["config"])
    assert c["name"] == entry["name"] and c["reduced"] == entry["reduced"]
    assert (bench_paths.BENCH / "configs" / c["reference"]).is_file()
    fam = family(c)
    for fn in ("program_config", "leaf_std", "prefill_flops",
               "decode_flops", "paged_attention_work",
               "flash_prefill_work"):
        assert callable(getattr(fam, fn)), fn
    traffic.load_mix(w["traffic"])
    assert "mean_sq_logit_gap" in harness.load_limits(cell)
    for trace in (False, True):
        ms = harness.metrics_of(SPEC, cell, trace)
        assert ms, (cell, trace)
        for m in ms:
            assert callable(harness.metric_reader(m["name"]).read)
    e2e = {m["name"] for m in harness.metrics_of(SPEC, cell, False)}
    assert "setup_s" in e2e and len(e2e) >= 2


@pytest.mark.parametrize("cell", CELLS)
def test_program_config_matches_the_file(cell):
    import harness
    c = harness.load_config(SPEC, harness.cell_of(SPEC, cell)["config"])
    cfg = family(c).program_config(c)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.resolved_head_dim, cfg.d_ff, cfg.vocab) == (
        c["num_hidden_layers"], c["hidden_size"], c["num_attention_heads"],
        c["num_key_value_heads"], c["head_dim"], c["intermediate_size"],
        c["vocab_size"])
    assert cfg.attn_impl == "pallas"


def test_names_units_and_metric_keys():
    names = [c["name"] for c in SPEC["configs"]] + CELLS + [
        m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        for cell in m.get("workloads", CELLS):
            moved = next(x for x in SPEC["end_to_end"]
                         if x["name"] == m["moves"])
            assert cell in moved.get("workloads", CELLS)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        for cell in m.get("workloads", []):
            assert cell in CELLS


def test_every_config_is_used_and_reduces_no_width():
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["name"] in used
        for k in c["reduced"]:
            assert not re.search(
                r"(hidden_size|intermediate|latent|state|projection|_dim$|"
                r"_rank$|expan|per_tok)", k), k
