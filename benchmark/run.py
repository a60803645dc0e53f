#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Cells, configurations, traffic mixes and per-layer metrics are found by
name in ``BENCHMARK.json`` at the checkout root.  Progress and the compared
numbers go to standard error; the last line of standard output is the
result object.  Without a TPU, or with fewer chips than the cell asks for,
the run exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    # Before jax is imported: the compile cache lives at a fixed path
    # inside the checkout, the TPU runtime writes no logs outside it, and
    # the configuration's program switches are in the environment.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".cache",
                                                           "jax")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    from benchmark import harness

    try:
        cellspec = harness.load_cell(ROOT, args.workload)
        os.environ.update(cellspec["config"]["knobs"])
        result = harness.run(cellspec, args.seed, args.seconds,
                             bool(args.trace), T_START)
    except harness.RunFailed as e:
        print(f"bench: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    for k, v in result["checks"].items():
        print(f"bench: check {k} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
