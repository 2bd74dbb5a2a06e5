#!/usr/bin/env python3
"""The readings the check's limits are set from, on the chip.

    python3 bench/calibrate.py --workload paper.poisson \\
        --seeds 101,102,103,104,105,106,107,108,109,110,111,112 --control 3

In one process, for each seed: one window unit through the run's own
set-up and timed path (``run.run_cell`` with a zero-second window), then
the run's comparison of that unit against the reference: the program's
readings. On the first ``--control`` seeds, the same lanes are compared
once more with the control (the reference in bfloat16) in the program's
place. One JSON line per reading; the last line gives, for each number,
the largest reading of the program and the smallest of the control. The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import check, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    c = run.load_cell(args.workload)
    run.import_program()
    worst, least = {}, {}
    for i, seed in enumerate(seeds):
        units = []
        t0 = time.perf_counter()
        res = run.run_cell(c, seed, 0.0, False, t0=t0, warm=i == 0,
                           keep=units)
        got = {k: v["value"] for k, v in res["check"].items()}
        for k, v in got.items():
            worst[k] = max(worst.get(k, v), v)
        print(json.dumps({"seed": seed, "side": "program", "correct":
                          res["correct"], "numbers": got,
                          "seconds": time.perf_counter() - t0}), flush=True)
        if i < args.control:
            t1 = time.perf_counter()
            ctl = check.compare(units, c["fleet"], c["traffic"], seed,
                                control=True)
            got = {k: v["value"] for k, v in ctl["numbers"].items()}
            for k, v in got.items():
                least[k] = min(least.get(k, v), v)
            print(json.dumps({"seed": seed, "side": "control", "correct":
                              ctl["correct"], "numbers": got,
                              "seconds": time.perf_counter() - t1}),
                  flush=True)
    print(json.dumps({"workload": args.workload, "program_max": worst,
                      "control_min": least}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
