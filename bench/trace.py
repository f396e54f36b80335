"""Reduction of a profiler trace to device busy/idle time, per-program and
per-operation device time, and the longest idle gaps with what the host
was doing.

Two steps, so the second can be tested on a recorded trace:

1. :func:`read_xspace` turns the ``.xplane.pb`` that ``jax.profiler``
   writes into a small dict: for each device plane its ``XLA Ops`` and
   ``XLA Modules`` lines, and the host spans whose names start with
   ``bench.`` (the harness's own ``TraceAnnotation`` spans).
2. :func:`reduce` takes that dict and gives the numbers.

Times in the dict are nanoseconds on the trace's clock, on which host and
device events share one timeline.
"""
from __future__ import annotations

import glob
import gzip
import json
import re

OPS, MODULES = "XLA Ops", "XLA Modules"
SPAN_PREFIX = "bench."


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``: the HLO
    instruction's name without its text."""
    return text.split(" = ", 1)[0].lstrip("%")


def read_xspace(path: str) -> dict:
    """``{"devices": [{"name", "ops": [[op, start, dur], ...],
    "modules": [...]}], "spans": [[name, start, dur], ...], "op_meta":
    {op: {"kernel": bool, "text": str, "stats": {...}}}}``. Ops are named
    by their HLO instruction; ``op_meta`` keeps, once per op, whether it
    is a TPU custom call (a Pallas kernel), the start of its text and its
    event stats."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans, meta = [], [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                if line.name == MODULES:
                    dev["modules"] = [[e.name, e.start_ns, e.duration_ns]
                                      for e in line.events]
                elif line.name == OPS:
                    for e in line.events:
                        name = op_name(e.name)
                        if name not in meta:
                            meta[name] = {
                                "kernel": "tpu_custom_call" in e.name,
                                "text": e.name[:400],
                                "stats": {k: str(v)[:400]
                                          for k, v in e.stats}}
                        dev["ops"].append([name, e.start_ns, e.duration_ns])
            if dev["ops"] or dev["modules"]:
                devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend([e.name, e.start_ns, e.duration_ns]
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1]),
            "op_meta": meta}


def find_xspace(log_dir: str) -> str:
    found = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def save(compact: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(compact, f)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(intervals):
    """Merged [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(events, lo, hi):
    return [(n, max(s, lo), min(s + d, hi)) for n, s, d in events
            if s + d > lo and s < hi]


def program_name(module: str) -> str:
    """``jit__decode_block(123)`` -> ``_decode_block``: the jitted
    function's name without the wrapper prefix and the program id."""
    name = re.sub(r"\(\d+\)$", "", module)
    return name[4:] if name.startswith("jit_") else name


KERNELS = {
    # fused unembed + select: (candidate, confidence) columns out
    "select": re.compile(r"^\S+ = \(s32\[\d+,1\]\{[^}]*\}, f32\[\d+,1\]"),
    # paged flash-decode: the page table is its first (prefetched) operand
    "paged_attn": re.compile(r"custom-call\(s32\[\d+,\d+\]"),
}


def kernel_kind(meta: dict):
    """Which of :data:`KERNELS` an op is (None if it is no TPU custom
    call or none of them), from the op's HLO text."""
    if not meta.get("kernel"):
        return None
    for kind, pat in KERNELS.items():
        if pat.search(meta["text"]):
            return kind
    return None


def _span_at(spans, t):
    """Innermost harness span containing time ``t`` (latest start)."""
    best = None
    for name, s, d in spans:
        if s <= t < s + d and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else None


def reduce(compact: dict, top: int = 10) -> dict:
    """Numbers of the window spanned by the ``bench.window`` host span
    (the whole trace if there is none), averaged over devices:

    ``window_s``, ``busy_s`` (union of op intervals), ``programs``
    {program: device seconds, from the modules line}, ``ops`` {op: device
    seconds}, ``op_events`` [[op, start, end], ...] of device 0,
    ``kernels`` {op: kind}, ``spans`` (the harness's), ``device_ops`` (the
    ``top`` ops by time) and ``idle_gaps`` (the ``top`` longest gaps,
    named by the programs around them and the harness span the host was
    in)."""
    spans = [tuple(s) for s in compact["spans"]]
    win = [s for s in spans if s[0] == "bench.window"]
    devices = compact["devices"]
    if not devices:
        raise ValueError("trace holds no device plane with XLA ops")
    if win:
        lo, hi = win[0][1], win[0][1] + win[0][2]
    else:
        evs = [e for d in devices for e in d["ops"]]
        lo = min(s for _, s, _ in evs)
        hi = max(s + d for _, s, d in evs)
    n = len(devices)
    busy, programs, ops = 0.0, {}, {}
    for dev in devices:
        op_ev = _clip(dev["ops"], lo, hi)
        busy += sum(e - s for s, e in _union((s, e) for _, s, e in op_ev))
        for name, s, e in op_ev:
            ops[name] = ops.get(name, 0.0) + (e - s) / n
        for name, s, e in _clip(dev["modules"], lo, hi):
            p = program_name(name)
            programs[p] = programs.get(p, 0.0) + (e - s) / n
    dev0 = devices[0]
    mods = sorted((program_name(m), s, e)
                  for m, s, e in _clip(dev0["modules"], lo, hi))
    mods.sort(key=lambda m: m[1])
    busy0 = _union((s, e) for _, s, e in _clip(dev0["ops"], lo, hi))
    edges = [lo] + [x for iv in busy0 for x in iv] + [hi]
    holes = sorted(((e - s, s, e) for s, e in zip(edges[0::2], edges[1::2])
                    if e > s), reverse=True)[:top]
    gaps = []
    for _, s, e in holes:
        inside = [m[0] for m in mods if m[1] <= s and e <= m[2]]
        before = [m[0] for m in mods if m[2] <= s]
        after = [m[0] for m in mods if m[1] >= e]
        where = (f"in {inside[-1]}" if inside else
                 f"{before[-1] if before else 'start'} -> "
                 f"{after[0] if after else 'end'}")
        host = _span_at(spans, (s + e) / 2)
        host = ("between steps" if host in (None, "bench.window")
                else re.sub(r"\.\d+$", "", host))
        gaps.append([f"{where} (host: {host})", (e - s) * 1e-9])
    to_s = 1e-9
    return {
        "window_s": (hi - lo) * to_s,
        "window": [lo, hi],
        "busy_s": busy / n * to_s,
        "programs": {k: v * to_s for k, v in programs.items()},
        "ops": {k: v * to_s for k, v in ops.items()},
        "spans": [list(x) for x in spans],
        "op_events": [[nm, s, e] for nm, s, e in _clip(dev0["ops"], lo, hi)],
        "kernels": {k: kernel_kind(v)
                    for k, v in compact.get("op_meta", {}).items()
                    if kernel_kind(v)},
        "device_ops": sorted(([k, v * to_s] for k, v in ops.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": gaps,
    }
