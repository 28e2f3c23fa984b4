"""The traffic generator on every mix file: seeds, clips, medians,
vocabulary and arrival counts."""

import json

import numpy as np
import pytest

import bench_paths
import traffic

MIXES = sorted(p.stem for p in (bench_paths.BENCH / "traffic").glob("*.json"))
VOCAB = 38016
BIG_SEED = 2**33 + 17


def lengths(sched):
    return sorted(r.prompt.shape[1] for r in sched.requests), \
        sorted(r.max_new for r in sched.requests)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_schedule(mix):
    m = traffic.load_mix(mix)
    a = traffic.schedule(m, VOCAB, BIG_SEED, 10.0)
    b = traffic.schedule(m, VOCAB, BIG_SEED, 10.0)
    assert [r.due_s for r in a.requests] == [r.due_s for r in b.requests]
    assert all(np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a.requests, b.requests))


@pytest.mark.parametrize("mix", MIXES)
def test_every_stretch_gets_the_same_work_in_another_order(mix):
    """Each run of BLOCK consecutive requests holds the same sizes and
    gaps for every seed; only their order within it differs."""
    m = traffic.load_mix(mix)
    a = traffic.schedule(m, VOCAB, 1, 30.0).requests
    b = traffic.schedule(m, VOCAB, 2, 30.0).requests
    B = traffic.BLOCK
    full = len(a) // B * B
    assert full >= B

    def blocks(reqs, key):
        return [sorted(key(r) for r in reqs[i:i + B])
                for i in range(0, full, B)]
    for key in (lambda r: r.prompt.shape[1], lambda r: r.max_new):
        assert blocks(a, key) == blocks(b, key)
        assert len({tuple(x) for x in blocks(a, key)}) == 1
    gaps = np.diff([0.0] + [r.due_s for r in a])
    gaps_b = np.diff([0.0] + [r.due_s for r in b])
    for i in range(0, full, B):
        assert sorted(gaps[i:i + B]) == pytest.approx(sorted(gaps_b[i:i + B]))
    assert [r.max_new for r in a] != [r.max_new for r in b]


@pytest.mark.parametrize("mix", MIXES)
def test_clips_medians_and_vocabulary(mix):
    m = traffic.load_mix(mix)
    s = traffic.schedule(m, VOCAB, 5, 40.0)
    plen, out = lengths(s)
    o = m["output"]
    assert o["min"] <= out[0] and out[-1] <= o["max"]
    if o["dist"] == "lognormal":
        assert np.median(out) == pytest.approx(o["median"], rel=0.07)
    p = m["prompt"]
    assert p["min"] <= plen[0] and plen[-1] <= p["max"]
    if p["dist"] == "lognormal":
        assert np.median(plen) == pytest.approx(p["median"], rel=0.07)
    ids = np.concatenate([r.prompt[0] for r in s.requests])
    assert ids.min() >= 2 and ids.max() < VOCAB
    assert max(r.prompt.shape[1] for r in s.requests) <= \
        traffic.max_prompt_len(m)


@pytest.mark.parametrize("mix", MIXES)
def test_arrival_count_at_the_rate(mix):
    m = traffic.load_mix(mix)
    s = traffic.schedule(m, VOCAB, 9, 30.0)
    a = m["arrivals"]
    if a["kind"] == "backlog":
        assert len(s.requests) == a["count"]
        assert all(r.due_s == 0.0 for r in s.requests)
    else:
        span = m["ramp_s"] + 30.0
        assert len(s.requests) == round(a["rate_per_s"] * span)
        assert s.requests[-1].due_s == pytest.approx(span, rel=0.1)
        due = [r.due_s for r in s.requests]
        assert due == sorted(due)


def test_lognormal_quantiles():
    q = traffic.quantiles({"dist": "lognormal", "median": 100, "sigma": 0.5,
                           "min": 1, "max": 10**6}, 1001)
    assert q[500] == 100 and q[0] < 30 and q[-1] > 400


def test_choice_lengths_follow_their_weights():
    q = traffic.quantiles({"dist": "choice", "values": [64, 512, 2048],
                           "weights": [2, 1, 1], "min": 1, "max": 1024},
                          traffic.BLOCK)
    assert list(np.bincount(np.searchsorted([64, 512, 1024], q))) == \
        [8, 4, 4]                       # 2048 clipped to the max


def test_gamma_arrivals_keep_the_rate_and_come_in_bursts():
    """Shape 0.25 (squared coefficient of variation 4): the mean gap is
    1 / rate, most gaps are far shorter and a few far longer."""
    m = {"arrivals": {"kind": "gamma", "rate_per_s": 2.0, "shape": 0.25},
         "ramp_s": 0.0,
         "prompt": {"dist": "fixed", "value": 64, "min": 1, "max": 64},
         "output": {"dist": "fixed", "value": 16, "min": 1, "max": 16}}
    s = traffic.schedule(m, VOCAB, BIG_SEED, 64.0)
    assert len(s.requests) == 128
    gaps = np.diff([0.0] + [r.due_s for r in s.requests])
    assert gaps.mean() == pytest.approx(0.5, rel=1e-9)
    assert np.median(gaps) < 0.2 and gaps.max() > 2.0
    assert gaps.std() / gaps.mean() == pytest.approx(2.0, rel=0.25)


def test_a_mix_can_name_a_generator_of_its_own(tmp_path, monkeypatch):
    """A mix that names ``generator`` is scheduled by that module,
    found by name beside the mix files, and sizes the scheduler by it."""
    (tmp_path / "twice.py").write_text(
        "import traffic\n"
        "def schedule(mix, vocab, seed, seconds):\n"
        "    s = traffic.schedule(mix['base'], vocab, seed, seconds)\n"
        "    for r in s.requests:\n"
        "        r.max_new *= 2\n"
        "    return s\n"
        "def max_prompt_len(mix):\n"
        "    return mix['base']['prompt']['max']\n"
        "def max_new_cap(mix):\n"
        "    return 2 * mix['base']['output']['max']\n")
    monkeypatch.setattr(traffic, "MIX_DIR", tmp_path)
    base = json.loads((bench_paths.BENCH / "traffic" / "chat.json")
                      .read_text())
    mix = {"generator": "twice", "base": base}
    a = traffic.schedule(base, VOCAB, 3, 10.0)
    b = traffic.schedule(mix, VOCAB, 3, 10.0)
    assert [2 * r.max_new for r in a.requests] == \
        [r.max_new for r in b.requests]
    assert traffic.max_new_cap(mix) == 2 * base["output"]["max"]
    assert traffic.max_prompt_len(mix) == base["prompt"]["max"]
    with pytest.raises(FileNotFoundError):
        traffic.schedule({"generator": "absent"}, VOCAB, 3, 10.0)


def test_unknown_arrival_kind_raises():
    with pytest.raises(ValueError):
        traffic.request_count({"kind": "lunar"}, 10.0)
