"""Readings that set a cell's correctness limit: for each seed, one short
run of the cell's own traffic, then the reference check of what the
window served, with the float8 control read at the same positions.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 \
        [--slacks 0.02 0.005]

One process on the chip; one JSON line per seed with the program's widest
logit gap (the lower reading), the control's (the upper reading), the
tokens compared, and ``correct`` for each, judged alike against the cell's
limits. With ``--slacks``, one more line per seed and slack: the same
readings of the same served requests at that order slack, with the
tokens of tau > 0 requests read by refinement step. The benchmark's own
runs do not run the control.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import harness  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--slacks", type=float, nargs="*", default=[])
    args = ap.parse_args()
    harness.add_paths()
    root = os.path.dirname(harness.BENCH_DIR)
    cell = harness.Cell.load(os.path.join(root, "BENCHMARK.json"),
                             args.workload)
    t = T_START
    for seed in args.seeds:
        out = harness.run(cell, seed, args.seconds, False, t, control=True)
        d = out["diag"]
        print(json.dumps({
            "seed": seed, "slack": cell.limits["slack"],
            "logit_gap": out["result"]["checks"]["logit_gap"]["value"],
            "control_gap": d["control_gap"],
            "tokens": out["result"]["checks"]["tokens"]["value"],
            "tau_reads_by_step": d["tau_reads_by_step"],
            "check_s": d["check_s"], "setup_s": d["setup_s"],
            "correct": out["result"]["correct"],
            "control_correct": d["control_correct"]}), flush=True)
        failed = out["result"]["failed"]
        for slack in args.slacks:
            res = harness.check(cell, out["weights"], out["picked"], True,
                                slack=slack)
            print(json.dumps({
                "seed": seed, "slack": slack, "logit_gap": res["gap"],
                "control_gap": res["control_gap"], "tokens": res["tokens"],
                "tau_reads_by_step": res["tau_reads_by_step"],
                "correct": harness.judge(failed, res["gap"], res["tokens"],
                                         cell.limits),
                "control_correct": harness.judge(
                    failed, res["control_gap"], res["tokens"],
                    cell.limits)}), flush=True)
        del out
        gc.collect()
        t = time.perf_counter()


if __name__ == "__main__":
    main()
