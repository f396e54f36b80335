"""Reductions shared by the per-layer readers in ``bench/metrics/``.

Each reader file holds one ``read(view) -> float | None`` for one metric;
a reader that finds nothing to read returns None and the metric is left
out of the result line. ``view`` is a :class:`bench.harness.RunView`.
"""
from __future__ import annotations

import re

import numpy as np

from bench import cost


def finished_in_window(view):
    """Requests whose final block came inside the window."""
    return [r for r in view.reqs if r.output is not None
            and view.ws <= r.blocks[-1][0] < view.we]


def iters_per_block(view):
    reqs = finished_in_window(view)
    if not reqs:
        return None
    return (sum(r.output.steps for r in reqs)
            / sum(len(r.blocks) for r in reqs))


def program_share(view, program: str):
    """Device seconds of one jitted program over the traced window, %."""
    t = view.trace
    if not t or program not in t["programs"]:
        return None
    return 100.0 * t["programs"][program] / t["window_s"]


def idle_share(view):
    t = view.trace
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def _kernel_calls(t, kind):
    return [(s, e) for name, s, e in t["op_events"]
            if t["kernels"].get(name) == kind]


def select_roofline(view):
    """Fused select: least time for every traced call (the real V, the
    rows as given) over their device time, %."""
    t = view.trace
    if not t or view.peaks is None:
        return None
    calls = _kernel_calls(t, "select")
    if not calls:
        return None
    s = view.cell.serve
    need = cost.min_seconds(cost.select_call(
        view.cell.model, s["max_batch"] * s["block_size"]), view.peaks)
    return 100.0 * need * len(calls) / (sum(e - s for s, e in calls) * 1e-9)


def _whole_steps(view):
    """(step index, start, end) of the steps whose ``bench.step.<n>``
    span lies wholly inside the traced window."""
    t = view.trace
    lo, hi = t["window"]
    out = []
    for name, s, d in t["spans"]:
        m = re.fullmatch(r"bench\.step\.(\d+)", name)
        if m and lo <= s and s + d <= hi:
            out.append((int(m.group(1)), s, s + d))
    return out


def paged_attn_roofline(view):
    """Paged decode attention: for each step traced whole, the work of the
    lanes that decoded a block (their real contexts, every layer, every
    forward of the step: one per select call, plus the commit) against
    the kernel's device time in that step, %."""
    t = view.trace
    if not t or view.peaks is None:
        return None
    m, s = view.cell.model, view.cell.serve
    attn, sel = _kernel_calls(t, "paged_attn"), _kernel_calls(t, "select")
    need = spent = 0.0
    for n, lo, hi in _whole_steps(view):
        inside = [(a, b) for a, b in attn if lo <= (a + b) / 2 < hi]
        forwards = sum(1 for a, b in sel if lo <= (a + b) / 2 < hi) + 1
        blocks = view.all_steps[n].blocks
        if not inside or not blocks:
            continue
        c = cost.paged_attn_call(m, s["block_size"],
                                 [s["prompt_len"] + st for _, _, st in blocks])
        k = forwards * m["n_layers"]
        need += cost.min_seconds({"flops": c["flops"] * k,
                                  "bytes": c["bytes"] * k}, view.peaks)
        spent += sum(b - a for a, b in inside) * 1e-9
    return 100.0 * need / spent if spent > 0 else None


def step_mfu(view):
    """FLOPs of the live lanes' prefill, refinement and commit tokens of
    the blocks delivered in the window, over window x peak, %."""
    if view.peaks is None:
        return None
    m, s = view.cell.model, view.cell.serve
    P, B = s["prompt_len"], s["block_size"]
    ratios = [r.output.steps / len(r.blocks) for r in view.reqs
              if r.output is not None]
    if not ratios:
        return None
    mean_ratio = float(np.mean(ratios))
    flops = 0.0
    for r in view.reqs:
        it = (r.output.steps / len(r.blocks) if r.output is not None
              else mean_ratio)
        for t, idx, _ in r.blocks:
            if not view.ws <= t < view.we:
                continue
            ctx = P + idx * B
            if idx == 0:
                flops += cost.forward_flops(m, P, 0, unembed=False)
            flops += it * cost.forward_flops(m, B, ctx, unembed=True)
            flops += cost.forward_flops(m, B, ctx, unembed=False)
    return 100.0 * flops / ((view.we - view.ws)
                            * view.peaks["bf16_flops_per_s"])
