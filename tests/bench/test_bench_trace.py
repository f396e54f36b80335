"""The reduction from a profiler trace to device busy/idle time, program
and kernel time and idle gaps: on a hand-made trace with known answers,
on a trace recorded on one TPU v5e (a few qwen2-0.5b steps, trimmed), and
the reading of an ``.xplane.pb`` that ``jax.profiler`` wrote here."""
import os
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path.insert(0, ROOT)

from bench import trace as tr  # noqa: E402

MS = 1_000_000  # ns


def hand_made():
    ops = [["fusion.1", 1 * MS, 2 * MS], ["fusion.2", 2 * MS, 2 * MS],
           ["_select_kernel", 6 * MS, 1 * MS],
           ["fusion.1", 9 * MS, 3 * MS]]          # runs past the window
    mods = [["jit__admit(7)", 1 * MS, 3 * MS],
            ["jit__decode_block(9)", 6 * MS, 5 * MS]]
    spans = [["bench.window", 0, 10 * MS], ["bench.step.0", 0, 5 * MS],
             ["bench.step.1", 5 * MS, 5 * MS]]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": mods}], "spans": spans}


def test_reduce_hand_made():
    r = tr.reduce(hand_made())
    assert r["window_s"] == pytest.approx(0.010)
    # ops cover [1,4) [6,7) [9,10) inside the window
    assert r["busy_s"] == pytest.approx(0.005)
    assert r["programs"] == {"_admit": pytest.approx(0.003),
                             "_decode_block": pytest.approx(0.004)}
    assert r["ops"]["fusion.1"] == pytest.approx(0.003)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.003)]
    # idle: [0,1) [4,6) [7,9); the longest first, named by its neighbours
    assert [g[1] for g in r["idle_gaps"]] == pytest.approx(
        [0.002, 0.002, 0.001])
    assert [g[0] for g in r["idle_gaps"]] == [
        "in _decode_block (host: bench.step)",
        "_admit -> _decode_block (host: bench.step)",
        "start -> _admit (host: bench.step)"]


def test_program_name():
    assert tr.program_name("jit__decode_block(123)") == "_decode_block"
    assert tr.program_name("jit_fn") == "fn"
    assert tr.program_name("other") == "other"


def test_read_xspace_keeps_harness_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.step.0"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    c = tr.read_xspace(tr.find_xspace(str(tmp_path)))
    assert [s[0] for s in c["spans"]] == ["bench.step.0"]
    assert c["spans"][0][2] > 0
    # the CPU has no device plane: nothing to reduce
    with pytest.raises(ValueError):
        tr.reduce(c)


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "qwen_sat_trace.json.gz")


def _busy_by_sweep(events, lo, hi):
    """Busy time counted a second way: a sweep over start/end edges."""
    edges = []
    for _, s, d in events:
        s, e = max(s, lo), min(s + d, hi)
        if e > s:
            edges += [(s, 1), (e, -1)]
    busy, depth, last = 0, 0, None
    for t, step in sorted(edges, key=lambda x: (x[0], -x[1])):
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_reduce_recorded_chip_trace():
    """0.85 s of qwen2-0.5b.sat-tau0.9 on one TPU v5e: the end of one
    block step, an admission and most of the next step."""
    c = tr.load(RECORDED)
    r = tr.reduce(c)
    lo, hi = r["window"]
    assert r["window_s"] == pytest.approx(0.85)
    assert r["busy_s"] == pytest.approx(
        _busy_by_sweep(c["devices"][0]["ops"], lo, hi) * 1e-9)
    assert 0.9 < r["busy_s"] / r["window_s"] < 1.0
    assert {"_admit", "_decode_block"} <= set(r["programs"])
    assert sorted(set(r["kernels"].values())) == ["paged_attn", "select"]
    gaps = [g[1] for g in r["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True) and len(gaps) == 10
    assert sum(gaps) <= r["window_s"] - r["busy_s"] + 1e-9
    assert len(r["device_ops"]) == 10


def test_rooflines_on_recorded_trace_stay_under_the_peak():
    """Even with every lane of the traced steps at the longest context,
    the kernels' readings stay under 100%."""
    import json
    from types import SimpleNamespace

    from bench import readers
    from bench.peaks import peaks
    with open(os.path.join(ROOT, "bench", "configs",
                           "qwen2-0.5b.json")) as f:
        config = json.load(f)
    from bench.spec import MODEL_KEYS
    cell = SimpleNamespace(model={k: config[k] for k in MODEL_KEYS},
                           serve=config["serve"])
    longest = [(i, 7, 224) for i in range(16)]   # block 7: context 736
    steps = {n: SimpleNamespace(blocks=longest) for n in range(20)}
    view = SimpleNamespace(trace=tr.reduce(tr.load(RECORDED)), cell=cell,
                           peaks=peaks("TPU v5 lite"), all_steps=steps)
    sel = readers.select_roofline(view)
    att = readers.paged_attn_roofline(view)
    assert 0 < sel < 100 and 0 < att < 100
