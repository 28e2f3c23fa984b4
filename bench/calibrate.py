#!/usr/bin/env python3
"""Many runs of one cell in one process, for calibration on the chip.

    python3 bench/calibrate.py --workload olmo-1b.chat \
        --seeds 101,102,103 --seconds 10 [--control] [--rates 2,3,4]

For each seed (and each rate, when ``--rates`` overrides the mix's
arrival rate) it runs the cell as ``bench/run.py`` does and prints one
JSON line with the compared numbers and the latency statistics. With
``--control`` the fp8 control takes the program's place in each run's
comparison (its ``checks`` and ``correct``), and the program's own
checks on the same sample come under ``program_checks``.
Lines are appended to ``chiprun_out/calibrate_<workload>.jsonl`` too. The
benchmark's own runs never call this.
"""

import time

import argparse
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    import harness
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    out_dir = ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    for rate in rates:
        for seed in [int(s) for s in args.seeds.split(",")]:
            out, record = harness.run_cell(
                args.workload, seed, args.seconds, False,
                t_start=time.perf_counter(), control=args.control,
                rate=rate)
            line = {"workload": args.workload, "seed": seed, "rate": rate,
                    **out, "stats": harness.run_stats(record)}
            print(json.dumps(line), flush=True)
            with open(out_dir / f"calibrate_{args.workload}.jsonl", "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
