"""The one traffic generator: reads a mix file, returns a schedule.

A mix (``bench/traffic/<name>.json``) holds parameters only: the
arrival process and its rate, prompt and output length distributions,
the ramp, the serving settings and the limits. This module turns a
mix, a vocabulary size, ``--seed`` and ``--seconds`` into requests.

Arrival processes (``arrivals.kind``): ``poisson`` (``rate_per_s``),
``gamma`` (a renewal process at ``rate_per_s`` whose gaps are gamma
distributed with ``shape``; a shape under 1 gives bursts, as BurstGPT
fits, arXiv:2401.17644) and ``backlog`` (``count`` requests due at
t=0). Length distributions (``dist``): ``lognormal`` (``median``,
``sigma``), ``uniform``, ``fixed`` (``value``) and ``choice``
(``values`` with ``weights``, e.g. a histogram read from a trace); all
are clipped to ``min`` and ``max``.

Every seed gets the same work, in every stretch of the stream. Sizes
and inter-arrival gaps are the quantiles of their distributions at
``(i + 0.5) / BLOCK``, repeated: each run of ``BLOCK`` consecutive
requests holds the same prompt lengths, output lengths and gaps, which
the seed permutes within the run; the seed also draws the token ids.
A server above capacity serves only the first few dozen requests of a
window, so a permutation of the whole stream would change the work
served from seed to seed (on the chip it moved ``olmo-1b.chat``'s
tokens per second between 28.6 and 48.6 with identical readings for
repeated seeds).

A mix whose shape these parameters cannot state names a generator of
its own, ``"generator": "<name>"``: the module
``bench/traffic/<name>.py``, found by name, whose
``schedule(mix, vocab, seed, seconds)`` returns a ``Schedule`` and
whose ``max_prompt_len(mix)`` and ``max_new_cap(mix)`` give the sizes
the scheduler is built for; it may give a request an SLO class of its
own (``Request.slo_class``), as a mix of interactive and batch clients
needs.
"""

from __future__ import annotations

import dataclasses
import json
import math
import pathlib
import statistics
from typing import List, Optional

import numpy as np

from loader import load_module

MIX_DIR = pathlib.Path(__file__).resolve().parent / "traffic"
BLOCK = 16


@dataclasses.dataclass
class Request:
    rid: int
    due_s: float             # seconds after the schedule starts
    prompt: np.ndarray       # (1, L) int32
    max_new: int
    slo_class: Optional[str] = None  # None: the mix's serve.slo_class


@dataclasses.dataclass
class Schedule:
    requests: List[Request]
    window_start_s: float            # ramp length
    window_s: float


def load_mix(name: str) -> dict:
    path = MIX_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix file {path}")
    return json.loads(path.read_text())


def _custom(mix: dict):
    """The mix's own generator module, or None for this one."""
    name = mix.get("generator")
    return load_module(MIX_DIR / f"{name}.py") if name else None


def _midpoints(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` integer sizes at the midpoint quantiles of ``dist``."""
    u = _midpoints(n)
    kind = dist["dist"]
    if kind == "lognormal":
        nd = statistics.NormalDist()
        z = np.array([nd.inv_cdf(float(p)) for p in u])
        x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
        x = np.rint(x)
    elif kind == "uniform":
        lo, hi = dist["min"], dist["max"]
        x = lo + np.floor(u * (hi - lo + 1))
    elif kind == "fixed":
        x = np.full(n, dist["value"], np.float64)
    elif kind == "choice":
        w = np.asarray(dist["weights"], np.float64)
        cdf = np.cumsum(w) / w.sum()
        x = np.asarray(dist["values"], np.float64)[np.searchsorted(cdf, u)]
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(x, dist["min"], dist["max"]).astype(np.int64)


def exponential_gaps(arr: dict, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps at the midpoint quantiles of Exp(rate)."""
    return -np.log1p(-_midpoints(n)) / float(arr["rate_per_s"])


def gamma_gaps(arr: dict, n: int) -> np.ndarray:
    """``n`` gaps at the midpoint quantiles of a gamma distribution of
    shape ``arr["shape"]`` (squared coefficient of variation
    ``1 / shape``), scaled so that their mean is ``1 / rate``: a few
    midpoints leave out much of a heavy tail's mass."""
    from scipy.stats import gamma
    g = gamma.ppf(_midpoints(n), float(arr["shape"]))
    return g / g.mean() / float(arr["rate_per_s"])


# arrival kind -> the quantile gaps of one block (None: all due at t=0)
GAPS = {"poisson": exponential_gaps, "gamma": gamma_gaps, "backlog": None}


def request_count(arr: dict, span_s: float) -> int:
    if arr["kind"] not in GAPS:
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    if GAPS[arr["kind"]] is None:
        return int(arr["count"])
    return max(1, int(round(float(arr["rate_per_s"]) * span_s)))


def blocked(block: np.ndarray, n: int, rng) -> np.ndarray:
    """``block`` repeated to length ``n``, permuted within each repeat."""
    reps = -(-n // len(block))
    return np.concatenate([rng.permutation(block) for _ in range(reps)])[:n]


def max_prompt_len(mix: dict) -> int:
    gen = _custom(mix)
    return gen.max_prompt_len(mix) if gen else mix["prompt"]["max"]


def max_new_cap(mix: dict) -> int:
    gen = _custom(mix)
    return gen.max_new_cap(mix) if gen else mix["output"]["max"]


def schedule(mix: dict, vocab: int, seed: int, seconds: float) -> Schedule:
    gen = _custom(mix)
    if gen:
        return gen.schedule(mix, vocab, seed, seconds)
    rng = np.random.default_rng(seed)
    ramp = float(mix.get("ramp_s", 0.0))
    arr = mix["arrivals"]
    n = request_count(arr, ramp + seconds)
    gaps = GAPS[arr["kind"]]
    if gaps is None:
        due = np.zeros(n)
    else:
        due = np.cumsum(blocked(gaps(arr, BLOCK), n, rng))
    out_len = blocked(quantiles(mix["output"], BLOCK), n, rng)
    p_len = blocked(quantiles(mix["prompt"], BLOCK), n, rng)
    reqs = []
    for i in range(n):
        ids = rng.integers(2, vocab, int(p_len[i])).astype(np.int32)
        reqs.append(Request(rid=i, due_s=float(due[i]), prompt=ids[None, :],
                            max_new=int(out_len[i])))
    return Schedule(requests=reqs, window_start_s=ramp,
                    window_s=float(seconds))
