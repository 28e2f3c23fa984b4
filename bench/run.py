#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process holds.

    python3 bench/run.py --workload olmo-1b.chat --seed 12345 \
        --seconds 10 --trace 0

Reads ``BENCHMARK.json`` for the cell, builds weights and traffic from
``--seed``, serves the cell's traffic through the program's streaming
stack for ``--seconds`` after its set-up, checks a sample of the served
tokens against the plain reference, and prints one JSON line last on
standard output: ``correct``, ``attempted``, ``failed``, ``metrics``
(end-to-end with ``--trace 0``, per-layer with ``--trace 1``),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``, each
compared number beside its limit. Without a TPU, or with fewer chips
than the cell needs, it exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: the fp8 reference takes the program's place "
                         "in the comparison, which must then read "
                         "correct false (calibration only; the "
                         "benchmark's runs leave it 0)")
    return ap


def main(argv=None) -> int:
    args = parser().parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"[bench] no program under {ROOT / 'src'}: nothing to "
              f"measure", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    import harness
    try:
        out, _ = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START,
                                  control=bool(args.control))
    except harness.NoChip as exc:
        print(f"[bench] {exc}", file=sys.stderr)
        return 3
    for k, v in out["checks"].items():
        harness.say(f"check {k} {v['value']} limit {v['limit']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
