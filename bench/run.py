"""The benchmark's command: one run of one cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. With ``--trace 0`` the last line of
standard output is one JSON object with the cell's end-to-end metrics;
with ``--trace 1`` a separate run traces the first seconds of the window
with ``jax.profiler`` and reports the per-layer metrics. The numbers the
correctness check compared are the last lines of standard error and the
``checks`` key of the result. Without a TPU it exits non-zero and prints
no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import harness  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    harness.add_paths()
    root = os.path.dirname(harness.BENCH_DIR)
    cell = harness.Cell.load(os.path.join(root, "BENCHMARK.json"),
                             args.workload)
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      T_START)
    res = out["result"]
    print(f"bench: {args.workload} seed={args.seed} "
          + json.dumps(out["diag"]), file=sys.stderr)
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
