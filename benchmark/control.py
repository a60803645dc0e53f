"""The control of ``correct``: the reference put in the program's place,
computed in bfloat16 (the step below the configuration's float32), and
judged by the same comparison and limits as a run.

    python3 -m benchmark.control --workload <name> --seeds 1 2 3

For each seed it draws the run's pool and the run's sample of pixels from
a window of the run's size, computes their records in float64 (the
reference) and in bfloat16 (standing for the stored rows), and prints the
compared numbers beside their limits, one JSON line per seed.  A control
that does not fail a limit means the comparison cannot tell a program
that computes in bfloat16 from a sound one.  The benchmark's own runs do
not run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(cellspec: dict, seed: int, seconds: float) -> dict:
    from benchmark import compare, harness, traffic

    config, mix = cellspec["config"], cellspec["mix"]
    C = int(config["driver"]["chips_per_batch"])
    pool = traffic.pool(config, mix, seed, int(config["pool_archives"]))
    n = C * harness.window_batches(cellspec, seconds, C)
    win_ids = [(i, 0) for i in range(C, C + n)]
    archive = lambda cx, cy: pool[cx % len(pool)]
    out = {}
    for precision in ("float64", "bfloat16"):
        out[precision] = harness.sample_records(config, seed, archive,
                                                win_ids, precision)[2]
    nums = compare.compare(out["bfloat16"], out["float64"])
    nums["missing_rows"] = 0.0
    return nums


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    from benchmark import harness

    cellspec = harness.load_cell(ROOT, args.workload)
    seconds = cellspec["spec"]["run_seconds"]
    lim = harness.limits(ROOT, args.workload)
    for seed in args.seeds:
        t0 = time.perf_counter()
        nums = readings(cellspec, seed, seconds)
        failed = sorted(k for k in lim if nums[k] > lim[k])
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "readings": nums, "limits": lim,
                          "fails": failed,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
