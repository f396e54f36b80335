"""Device idle time put down to the program's own phases, and the program's
work counters over a window.

The serving program marks its phases with host spans prefixed ``cdlm.``
(``repro.serving.tracing``) and counts its work in
``ContinuousEngine.counters()``. The benchmark's reduction
(``bench/trace.py``) keeps the harness's ``bench.`` spans only, and a run
reads no counters; the functions here take what it leaves out:

- :func:`read_program_spans`: the ``cdlm.`` host spans of an
  ``.xplane.pb``, as ``[name, start_ns, duration_ns]``;
- :func:`idle_by_span`: device-0 idle seconds of a reduced trace
  (``bench.trace.reduce``) by the innermost program span the host was in;
  :func:`idle_in_step_share` the part inside ``cdlm.step`` and not asleep
  in it; :func:`label_gaps` the reduction's longest idle gaps, each
  labelled with the program span at its midpoint;
- :func:`counters_between`: the change of ``engine.counters()`` over a
  window, from snapshots taken after each step, and three ratios of it:
  :func:`step_host_ms`, :func:`lane_iter_useful` and
  :func:`admit_lanes_per_prefill`.

Run as a command, it makes one traced run of a cell (as ``bench/run.py
--trace 1`` does) and prints all of these as one JSON line:

    python3 bench/attribution.py --workload qwen2-0.5b.poisson-tau0 \\
        --seed 7 --seconds 51 --out attribution.json
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from unittest import mock  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import trace as tr  # noqa: E402

PREFIX = "cdlm."
STEP, SLEEP = "cdlm.step", "cdlm.step.sleep"


def read_program_spans(path: str) -> list:
    """The program's host spans in an ``.xplane.pb``, sorted by start."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans = [[e.name, e.start_ns, e.duration_ns]
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(PREFIX)]
    return sorted(spans, key=lambda s: s[1])


def device_scopes(path: str) -> dict:
    """What a trace shows of the device scopes: the device planes' line
    names, and how many device events name a ``cdlm.`` scope in their
    name or their stats."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    lines, named, example = set(), 0, None
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            lines.add(line.name)
            for e in line.events:
                text = e.name + " " + " ".join(f"{k}={v}"
                                               for k, v in e.stats)
                if PREFIX in text:
                    named += 1
                    example = example or [line.name, text[-400:]]
    return {"lines": sorted(lines), "events_naming_a_scope": named,
            "example": example}


def _innermost(spans):
    """The innermost of spans that all cover one instant: spans of one
    thread nest, so the latest start, and of those the shortest."""
    return max(spans, key=lambda s: (s[1], -s[2]))[0]


def span_at(spans, t):
    """The innermost span covering time ``t``, or None."""
    over = [s for s in spans if s[1] <= t < s[1] + s[2]]
    return _innermost(over) if over else None


def _segments(spans) -> list:
    """``[(start, end, name)]``: the timeline cut where any span starts or
    ends, each piece named by the innermost span over it, pieces under no
    span left out."""
    edges = sorted({x for _, s, d in spans for x in (s, s + d)})
    out, i, active = [], 0, []
    ordered = sorted(spans, key=lambda s: s[1])
    for lo, hi in zip(edges, edges[1:]):
        while i < len(ordered) and ordered[i][1] <= lo:
            active.append(ordered[i])
            i += 1
        active = [s for s in active if s[1] + s[2] > lo]
        if active:
            out.append((lo, hi, _innermost(active)))
    return out


def idle_intervals(t: dict) -> list:
    """Device-0 idle ``(start, end)`` in ns inside the reduced trace's
    window, as ``bench.trace.reduce`` finds its gaps."""
    lo, hi = t["window"]
    busy = tr._union((s, e) for _, s, e in t["op_events"])
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]


def idle_by_span(t: dict, spans: list) -> dict:
    """Device-0 idle seconds of the window by the innermost program span
    the host was in; ``None`` collects the idle under no program span."""
    out: dict = {}
    segs = _segments(spans)
    j = 0
    for s, e in idle_intervals(t):
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        covered, k = 0, j
        while k < len(segs) and segs[k][0] < e:
            part = min(e, segs[k][1]) - max(s, segs[k][0])
            if part > 0:
                out[segs[k][2]] = out.get(segs[k][2], 0.0) + part * 1e-9
                covered += part
            k += 1
        out[None] = out.get(None, 0.0) + (e - s - covered) * 1e-9
    return out


def idle_in_step_share(t: dict, spans: list):
    """Device-0 idle while the host was inside ``cdlm.step`` and not in
    its sleep to the next arrival, over the window, %; None without
    program spans."""
    if not t or not spans:
        return None
    split = idle_by_span(t, spans)
    idle = sum(v for k, v in split.items()
               if k is not None and k.startswith(STEP) and k != SLEEP)
    return 100.0 * idle / t["window_s"]


def label_gaps(t: dict, spans: list) -> list:
    """The reduction's ``idle_gaps``, each label followed inside its
    brackets by the innermost program span at the gap's midpoint (left as
    it was where none covers it)."""
    holes = sorted(((e - s, s, e) for s, e in idle_intervals(t)),
                   reverse=True)[:len(t["idle_gaps"])]
    out = []
    for (label, secs), (d, s, e) in zip(t["idle_gaps"], holes):
        assert abs(d * 1e-9 - secs) < 1e-12, (label, secs, d)
        inner = span_at(spans, (s + e) / 2)
        if inner is not None and label.endswith(")"):
            label = f"{label[:-1]} / {inner})"
        out.append([label, secs])
    return out


def _snapshot(log, t):
    """The counters after the last step that ended by ``t``."""
    before = [c for at, c in log if at <= t]
    return before[-1] if before else None


def counters_between(log: list, ws: float, we: float):
    """The change of the counters over ``[ws, we)`` from ``log``, a list of
    ``(time, engine.counters())`` taken after each step; None if no step
    ended in the window."""
    c0, c1 = _snapshot(log, ws), _snapshot(log, we)
    if c1 is None or c1 is c0:
        return None
    if c0 is None:
        c0 = {k: ({p: 0.0 for p in v} if isinstance(v, dict) else 0)
              for k, v in c1.items()}
    out = {k: v - c0[k] for k, v in c1.items() if k != "host_s"}
    out["host_s"] = {p: v - c0["host_s"][p] for p, v in c1["host_s"].items()}
    return out


def step_host_ms(d):
    """Host milliseconds per decode step spent in ``step()`` outside the
    wait for the device and the sleep to the next arrival."""
    if not d or not d["steps"]:
        return None
    h = d["host_s"]
    return 1e3 * (h["step"] - h["sync"] - h["sleep"]) / d["steps"]


def lane_iter_useful(d, max_batch: int):
    """Share of the refinement loop's lane-iterations in which the lane
    still had a masked position, %."""
    if not d or not d["refine_forwards"]:
        return None
    return 100.0 * d["lane_iters_active"] / (d["refine_forwards"] * max_batch)


def admit_lanes_per_prefill(d):
    """Lanes admitted per admission prefill."""
    if not d or not d["admit_calls"]:
        return None
    return d["admitted_lanes"] / d["admit_calls"]


def span_cost_us(n: int = 100_000) -> float:
    """Host microseconds of one program span with the profiler off."""
    from repro.serving.tracing import PhaseClock
    clock = PhaseClock()
    t0 = time.perf_counter()
    for _ in range(n):
        with clock.span("emit", ids=""):
            pass
    return (time.perf_counter() - t0) / n * 1e6


def _median_ms(steps):
    return statistics.median(s.t1 - s.t0 for s in steps) * 1e3 if steps \
        else None


def log_counters(engine, log: list) -> None:
    """Wrap ``engine.step`` to append ``(time, engine.counters())`` after
    each step (a run's ``faults`` hook, so it wraps the harness's own step
    wrapper)."""
    inner = engine.step

    def step():
        events = inner()
        log.append((time.perf_counter(), engine.counters()))
        return events
    engine.step = step


def summary(view, spans: list, log: list) -> dict:
    """Everything above, for one traced run's ``RunView``: the idle split
    and the labelled gaps of its traced part, the counters over its
    window, and the median step time in the traced part and after it."""
    t = view.trace
    lo, hi = t["window"]
    traced = [view.all_steps[int(nm.rsplit(".", 1)[1])]
              for nm, s, d in t["spans"]
              if nm.startswith("bench.step.") and lo <= s and s + d <= hi]
    # the profiler stops a moment after the traced window closes
    tail = [s for s in view.steps
            if s.t0 > traced[-1].t1 + 1.0] if traced else []
    d = counters_between(log, view.ws, view.we)
    split = idle_by_span(t, spans)
    n_spans = sum(1 for s in spans if lo <= s[1] < hi)
    return {
        "window_s": t["window_s"],
        "idle_share": 100.0 * (1 - t["busy_s"] / t["window_s"]),
        "idle_in_step_share": idle_in_step_share(t, spans),
        "idle_by_span_ms": sorted(
            ([k or "(none)", v * 1e3] for k, v in split.items()),
            key=lambda x: -x[1]),
        "idle_gaps": label_gaps(t, spans),
        "device_ops": t["device_ops"],
        "counters": d,
        "step_host_ms": step_host_ms(d),
        "lane_iter_useful": lane_iter_useful(
            d, view.cell.serve["max_batch"]),
        "admit_lanes_per_prefill": admit_lanes_per_prefill(d),
        "host_ms_per_step": ({p: 1e3 * v / d["steps"]
                              for p, v in d["host_s"].items()}
                             if d and d["steps"] else None),
        "step_ms_median_traced": _median_ms(traced),
        "step_ms_median_untraced": _median_ms(tail),
        "steps_traced": len(traced), "steps_untraced": len(tail),
        "program_spans_per_traced_step": (n_spans / len(traced)
                                          if traced else None),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args()

    from bench import harness
    harness.add_paths()
    root = os.path.dirname(harness.BENCH_DIR)
    cell = harness.Cell.load(os.path.join(root, "BENCHMARK.json"),
                             args.workload)
    log, spans, scopes = [], [], {}
    read = tr.read_xspace

    def read_all(path):
        spans.extend(read_program_spans(path))
        scopes.update(device_scopes(path))
        return read(path)

    # the harness reads the trace it took through tr.read_xspace; this
    # reads the program's spans from the same file on the way
    with mock.patch.object(tr, "read_xspace", read_all):
        out = harness.run(cell, args.seed, args.seconds, True, T_START,
                          faults=lambda engine: log_counters(engine, log))
    res = out["result"]
    line = json.dumps(dict(
        workload=args.workload, seed=args.seed, correct=res["correct"],
        **summary(out["view"], spans, log), device_scopes=scopes,
        span_cost_us=span_cost_us(), per_layer=res["metrics"]))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)


if __name__ == "__main__":
    main()
