"""Sweep of open-loop arrival rates on one warmed engine, to find the
knee: the highest rate whose backlog does not grow over a window.

    python3 bench/knee.py --config <config> --traffic <open-loop mix> \\
        --seed <n> --seconds <s> --rates 20 30 40

One process, on the chip. For each rate it prints one JSON line: the
offered and completed request rates, the backlog (requests sent that have
no first block yet) at the middle and the end of the window, and the time
to first block. The chosen rate goes into the open-loop traffic file as a
number; the benchmark's runs never search for it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import harness, traffic  # noqa: E402


def backlog(reqs, t):
    return sum(1 for r in reqs if r.sent and r.sent <= t
               and not (r.blocks and r.blocks[0][0] <= t))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    harness.add_paths()
    name = f"{args.config}.{args.traffic}"
    with open(os.path.join(harness.BENCH_DIR, "configs",
                           args.config + ".json")) as f:
        config = json.load(f)
    bench = {"workloads": [{"name": name, "chips": 1}]}
    cell = harness.Cell(name, config,
                        traffic.load(harness.BENCH_DIR, args.traffic),
                        bench, {})
    sv = harness.serve(cell, args.seed, T_START)
    print(json.dumps({"setup_s": sv.setup_s}), flush=True)
    for rate in args.rates:
        # past the knee more requests wait than the cell's clients hold
        c = dataclasses.replace(cell, mix=dict(cell.mix, rate_per_s=rate,
                                               clients=512))
        load = harness.Load(c, sv.driver, args.seed, args.seconds)
        ws = load.start() + c.mix["lead_in_s"]
        we = ws + args.seconds
        time.sleep(max(0.0, we - time.perf_counter()))
        mid = backlog(load.reqs, ws + args.seconds / 2)
        end = backlog(load.reqs, we)
        # past the knee the queue outlives the window: let the lanes
        # drain briefly, then abort what is left so the next rate starts
        # from an empty engine
        load.finish(we + 5.0)
        for r in load.reqs:
            if not r.done.is_set() and r.rid >= 0:
                sv.driver.abort(r.rid)
        load.finish(time.perf_counter() + harness.DRAIN_S)
        e2e = harness.end_to_end(load.reqs, ws, we, c.serve["block_size"])
        done = sum(1 for r in load.reqs if r.output is not None
                   and ws <= r.blocks[-1][0] < we)
        print(json.dumps({
            "rate": rate, "completed_per_s": done / args.seconds,
            "backlog_mid": mid, "backlog_end": end,
            "ttfb_p95_ms": e2e.get("ttfb_p95_ms"),
            "gap_p95_ms": e2e.get("gap_p95_ms"), "tok_s": e2e["tok_s"],
            "late_max_ms": max((x for _, x in load.lateness),
                               default=0) * 1e3}),
            flush=True)
    sv.driver.shutdown()


if __name__ == "__main__":
    main()
