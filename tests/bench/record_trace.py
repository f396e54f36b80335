"""Records the trace that ``test_bench_trace.py`` reads: one traced run of a
cell on the chip, its reduced trace cut to a short stretch of the traced
window and written as gzip JSON.

    python3 tests/bench/record_trace.py --workload qwen2-0.5b.sat-tau0.9 \\
        --seed 7 --seconds 10 --start 0.3 --length 0.85 \\
        --out tests/bench/data/qwen_sat_trace.json.gz

``--start`` and ``--length`` are seconds from the start of the traced
window. What the cut keeps: every device op, program and host span that
overlaps it, and the op metadata of the ops kept; the window span is the
cut itself.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..")
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402
from bench import trace as tr  # noqa: E402


def cut(compact: dict, start: float, length: float) -> dict:
    window = next(s for s in compact["spans"] if s[0] == "bench.window")
    lo = window[1] + start * 1e9
    hi = lo + length * 1e9

    def keep(events):
        return [e for e in events if e[1] < hi and e[1] + e[2] > lo]

    devices = [dict(d, ops=keep(d["ops"]), modules=keep(d["modules"]))
               for d in compact["devices"]]
    names = {o[0] for d in devices for o in d["ops"]}
    spans = [["bench.window", lo, hi - lo]] + [
        s for s in keep(compact["spans"]) if s[0] != "bench.window"]
    return {"devices": devices, "spans": spans,
            "op_meta": {k: v for k, v in compact["op_meta"].items()
                        if k in names}}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--start", type=float, default=0.3)
    ap.add_argument("--length", type=float, default=0.85)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    harness.add_paths()
    cell = harness.Cell.load(os.path.join(ROOT, "BENCHMARK.json"),
                             args.workload)
    with tempfile.TemporaryDirectory() as d:
        whole = os.path.join(d, "trace.json.gz")
        harness.run(cell, args.seed, args.seconds, True, T_START,
                    keep_trace=whole)
        tr.save(cut(tr.load(whole), args.start, args.length), args.out)


if __name__ == "__main__":
    main()
