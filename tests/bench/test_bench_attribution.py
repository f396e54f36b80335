"""Idle time put down to the program's spans, and the program's counters over
a window (``bench/attribution.py``): on a hand-made trace and counter log
with known answers, on the reading of an ``.xplane.pb``, and on the trace
recorded on one TPU v5e, whose reduction must read as it did before the
program had spans."""
import os
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path.insert(0, ROOT)

from bench import attribution as at  # noqa: E402
from bench import trace as tr  # noqa: E402

MS = 1_000_000  # ns


def hand_made():
    """Device busy [1,4) [6,7) [9,10) ms of a 10 ms window, so idle [0,1)
    [4,6) [7,9); and the program's spans over it."""
    ops = [["fusion.1", 1 * MS, 3 * MS], ["fusion.2", 6 * MS, 1 * MS],
           ["fusion.3", 9 * MS, 1 * MS]]
    mods = [["jit__admit(7)", 1 * MS, 3 * MS],
            ["jit__decode_block(9)", 6 * MS, 5 * MS]]
    spans = [["bench.window", 0, 10 * MS], ["bench.step.0", 0, 5 * MS],
             ["bench.step.1", 5 * MS, 5 * MS]]
    program = [["cdlm.driver.wait", 0, MS // 2],
               ["cdlm.step", MS // 2, 8 * MS],            # to 8.5
               ["cdlm.step.pages", MS // 2, MS // 2],     # to 1
               ["cdlm.step.admit", 3 * MS, 5 * MS // 2],  # to 5.5
               ["cdlm.step.admit.keys", 9 * MS // 2, MS],  # 4.5 to 5.5
               ["cdlm.step.sleep", 7 * MS, 3 * MS // 2]]  # to 8.5
    compact = {"devices": [{"name": "/device:TPU:0", "ops": ops,
                            "modules": mods}], "spans": spans}
    return tr.reduce(compact), program


def test_idle_by_span_hand_made():
    t, program = hand_made()
    split = at.idle_by_span(t, program)
    want = {"cdlm.driver.wait": 0.0005, "cdlm.step.pages": 0.0005,
            "cdlm.step.admit": 0.0005, "cdlm.step.admit.keys": 0.001,
            "cdlm.step": 0.0005, "cdlm.step.sleep": 0.0015, None: 0.0005}
    assert split.keys() == want.keys()
    for k, v in want.items():
        assert split[k] == pytest.approx(v), k
    assert sum(split.values()) == pytest.approx(
        t["window_s"] - t["busy_s"])
    # inside the step and awake: pages, admit, keys and the step itself
    assert at.idle_in_step_share(t, program) == pytest.approx(25.0)
    assert at.idle_in_step_share(t, []) is None


def test_label_gaps_hand_made():
    t, program = hand_made()
    assert [g[0] for g in t["idle_gaps"]] == [
        "in _decode_block (host: bench.step)",
        "_admit -> _decode_block (host: bench.step)",
        "start -> _admit (host: bench.step)"]
    assert at.label_gaps(t, program) == [
        ["in _decode_block (host: bench.step / cdlm.step.sleep)",
         pytest.approx(0.002)],
        ["_admit -> _decode_block (host: bench.step / "
         "cdlm.step.admit.keys)", pytest.approx(0.002)],
        ["start -> _admit (host: bench.step / cdlm.step.pages)",
         pytest.approx(0.001)]]
    # a gap no program span covers keeps its label
    assert at.label_gaps(t, []) == t["idle_gaps"]


def _counters(steps, admit_calls, admitted, forwards, active, blocks, host):
    return {"steps": steps, "admit_calls": admit_calls,
            "admitted_lanes": admitted, "forwards": forwards,
            "refine_forwards": forwards - admit_calls - steps,
            "lane_iters_active": active, "blocks_emitted": blocks,
            "host_s": dict({"step": 0.0, "sync": 0.0, "sleep": 0.0,
                            "admit": 0.0}, **host)}


def test_counters_between_and_their_ratios():
    log = [(1.0, _counters(2, 1, 4, 10, 20, 3, {"step": 0.5})),
           (2.0, _counters(4, 2, 10, 30, 60, 9,
                           {"step": 1.5, "sync": 0.4, "sleep": 0.1})),
           (3.0, _counters(6, 4, 13, 60, 140, 20,
                           {"step": 2.5, "sync": 0.8, "sleep": 0.2,
                            "admit": 0.3}))]
    d = at.counters_between(log, 1.5, 3.5)
    assert d["steps"] == 4 and d["admit_calls"] == 3
    assert d["admitted_lanes"] == 9 and d["forwards"] == 50
    assert d["refine_forwards"] == 43 and d["lane_iters_active"] == 120
    assert d["host_s"]["step"] == pytest.approx(2.0)
    # (2.0 - 0.8 - 0.2) s over 4 steps
    assert at.step_host_ms(d) == pytest.approx(250.0)
    assert at.lane_iter_useful(d, 16) == pytest.approx(100 * 120 / (43 * 16))
    assert at.admit_lanes_per_prefill(d) == pytest.approx(3.0)
    # a window that opens before the first step counts from zero
    d0 = at.counters_between(log, 0.0, 1.0)
    assert d0["steps"] == 2 and d0["host_s"]["step"] == 0.5
    # no step ended inside the window, or no counters at all
    assert at.counters_between(log, 3.2, 3.9) is None
    assert at.counters_between([], 0.0, 9.0) is None
    for reader in (at.step_host_ms, at.admit_lanes_per_prefill):
        assert reader(None) is None
    assert at.lane_iter_useful(None, 16) is None
    idle = _counters(0, 0, 0, 0, 0, 0, {})
    assert at.step_host_ms(idle) is None
    assert at.lane_iter_useful(idle, 16) is None
    assert at.admit_lanes_per_prefill(idle) is None


def test_read_program_spans_keeps_the_programs_only(tmp_path):
    import jax
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.step.0"):
        with jax.profiler.TraceAnnotation("cdlm.step"):
            with jax.profiler.TraceAnnotation("cdlm.step.admit", ids="1 2"):
                pass
    jax.profiler.stop_trace()
    path = tr.find_xspace(str(tmp_path))
    spans = at.read_program_spans(path)
    assert [s[0] for s in spans] == ["cdlm.step", "cdlm.step.admit"]
    (_, s0, d0), (_, s1, d1) = spans
    assert s0 <= s1 and s1 + d1 <= s0 + d0
    # the CPU has no device plane, so no device scope
    assert at.device_scopes(path)["events_naming_a_scope"] == 0
    # the harness's reading is unchanged: its own spans only
    assert [s[0] for s in tr.read_xspace(path)["spans"]] == ["bench.step.0"]


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "qwen_sat_trace.json.gz")


def test_recorded_chip_trace_reduces_as_before():
    """The trace recorded before the program had spans reduces to the
    numbers it did then, and no label gains a program span."""
    r = tr.reduce(tr.load(RECORDED))
    assert r["window_s"] == pytest.approx(0.85)
    assert r["busy_s"] == pytest.approx(0.821971077)
    assert r["programs"]["_admit"] == pytest.approx(0.107941988)
    assert r["programs"]["_decode_block"] == pytest.approx(0.7115695940000001)
    assert r["device_ops"][:3] == [
        ["while.166", pytest.approx(0.692380667)],
        ["while.163", pytest.approx(0.536829608)],
        ["while.173", pytest.approx(0.376980865)]]
    assert r["idle_gaps"][:3] == [
        ["convert_element_type -> _decode_block (host: bench.step)",
         pytest.approx(0.005037478)],
        ["convert_element_type -> _decode_block (host: bench.step)",
         pytest.approx(0.004421485)],
        ["_lambda -> convert_element_type (host: between steps)",
         pytest.approx(0.003759049)]]
    assert at.label_gaps(r, []) == r["idle_gaps"]
    gaps = sum(e - s for s, e in at.idle_intervals(r)) * 1e-9
    assert gaps == pytest.approx(r["window_s"] - r["busy_s"])


def test_summary_of_a_recorded_run():
    """The command's summary over the recorded trace: a run without
    program spans reads its idle under no span and its gaps as labelled;
    the one step traced whole is timed, and later ones as untraced."""
    from types import SimpleNamespace
    r = tr.reduce(tr.load(RECORDED))
    steps = [SimpleNamespace(t0=i * 1.0, t1=i * 1.0 + 0.5 + i / 100)
             for i in range(20)]
    log = [(s.t1, _counters(i + 1, i, 2 * i, 30 * (i + 1), 100 * i, i,
                            {"step": 0.1 * (i + 1)}))
           for i, s in enumerate(steps)]
    view = SimpleNamespace(trace=r, all_steps=steps, steps=steps[10:],
                           ws=10.0, we=20.0, cell=SimpleNamespace(
                               serve={"max_batch": 16}))
    s = at.summary(view, [], log)
    assert s["idle_in_step_share"] is None
    assert s["idle_gaps"] == r["idle_gaps"]
    assert s["idle_by_span_ms"] == [
        ["(none)", pytest.approx((r["window_s"] - r["busy_s"]) * 1e3)]]
    assert s["steps_traced"] == 1
    assert s["step_ms_median_traced"] == pytest.approx(610.0)
    # steps 13-19 start a second after the traced one ends
    assert s["steps_untraced"] == 7
    assert s["counters"]["steps"] == 10
    assert s["admit_lanes_per_prefill"] == pytest.approx(2.0)
    assert s["step_host_ms"] == pytest.approx(100.0)


def test_counters_over_a_cpu_drive():
    """A one-second run of the tiny cell on the CPU with the counters
    logged after each step: the window's ratios lie in their ranges."""
    import time

    from bench import harness
    from test_bench_drive import tiny_cell
    log = []
    out = harness.run(tiny_cell(), 2147483659, 1.0, False,
                      time.perf_counter(), require_tpu=False,
                      faults=lambda engine: at.log_counters(engine, log))
    view = out["view"]
    d = at.counters_between(log, view.ws, view.we)
    assert abs(d["steps"] - len(view.steps)) <= 1
    assert d["blocks_emitted"] > 0 and d["forwards"] > d["steps"]
    assert 0 < at.lane_iter_useful(d, 4) <= 100
    assert 1 <= at.admit_lanes_per_prefill(d) <= 4
    assert 0 < at.step_host_ms(d)
    assert d["host_s"]["sync"] <= d["host_s"]["step"]
