"""The serving loop's own instrumentation: host spans on the profiler's
trace, the engine's work counters, the device scopes in the compiled
programs, and their export on ``/metrics``."""
import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ServeConfig
from repro.configs.registry import get_config
from repro.serving import ContinuousEngine, GenerationRequest, tracing
from repro.serving.api import SamplingParams
from repro.serving.server import EngineDriver

CFG = get_config("qwen2-0.5b").reduced(dtype="float32")
P, G, B = 8, 16, 4


def _engine(params, layout="paged", max_batch=2):
    serve = ServeConfig(max_batch=max_batch, block_size=B, gen_length=G,
                        sampler="cdlm", conf_threshold=0.5,
                        scheduler="continuous", cache_layout=layout)
    return ContinuousEngine(params, CFG, serve, prompt_len=P)


@pytest.fixture(scope="module")
def params():
    from repro.models import init_model
    return init_model(jax.random.PRNGKey(0), CFG)


def _requests(n, arrivals=None, max_tokens=None):
    rng = np.random.default_rng(3)
    return [GenerationRequest(
        prompt=rng.integers(2, CFG.vocab_size, P, dtype=np.int32), id=i,
        arrival_s=0.0 if arrivals is None else arrivals[i],
        params=SamplingParams(max_tokens=max_tokens[i] if max_tokens
                              else None))
        for i in range(n)]


def _host_events(log_dir):
    """``{thread: [(name, start, end, stats)]}`` of the ``cdlm.`` spans."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events
                   if e.name.startswith("cdlm.")]
            if evs:
                out.setdefault(line.name, []).extend(evs)
    return out


def _inside(ev, parents):
    return any(p[1] <= ev[1] and ev[2] <= p[2] for p in parents)


def test_step_spans_nest_in_their_step(params, tmp_path):
    """Every ``cdlm.step.*`` span lies inside a ``cdlm.step`` of the same
    thread, ``admit.keys`` inside an ``admit``; the admit and emit spans
    name the requests admitted and finished; the phases' host seconds add
    up inside the step's."""
    eng = _engine(params)
    eng.warmup()
    # the third request arrives after the first two finished: the engine
    # sleeps to it
    reqs = _requests(3, arrivals=[0.0, 0.0, 2.5], max_tokens=[4, 8, 4])
    jax.profiler.start_trace(str(tmp_path))
    outs = eng.generate(reqs)
    jax.profiler.stop_trace()
    assert sorted(o.id for o in outs) == [0, 1, 2]

    threads = _host_events(str(tmp_path))
    assert len(threads) == 1
    evs = next(iter(threads.values()))
    steps = [e for e in evs if e[0] == "cdlm.step"]
    children = [e for e in evs if e[0].startswith("cdlm.step.")]
    assert steps and children
    assert {e[0] for e in children} == {
        tracing.SPANS[p] for p in ("pages", "admit", "admit.keys", "decode",
                                   "sync", "emit", "evict", "sleep")}
    for e in children:
        assert _inside(e, steps), e[0]
    admits = [e for e in evs if e[0] == "cdlm.step.admit"]
    for e in evs:
        if e[0] == "cdlm.step.admit.keys":
            assert _inside(e, admits)

    def ids(name):
        return sorted(int(i) for e in evs if e[0] == name
                      for i in str(e[3]["ids"]).split())
    assert ids("cdlm.step.admit") == [0, 1, 2]
    assert ids("cdlm.step.emit") == [0, 1, 2]

    host = eng.counters()["host_s"]
    inner = sum(host[p] for p in ("pages", "admit", "decode", "sync",
                                  "emit", "evict", "sleep"))
    assert 0 < inner <= host["step"]
    assert host["admit.keys"] <= host["admit"]
    assert host["sleep"] > 0


@pytest.mark.parametrize("layout,max_batch", [("dense", 1), ("paged", 2)])
def test_counters_after_a_drain(params, layout, max_batch):
    """With no preemption, the active lane-iterations are the requests'
    own refinement steps, every request is admitted once, and the forwards
    are the device's count: one per admission, one commit per step, the
    rest refinement. With one lane every refinement forward is active."""
    eng = _engine(params, layout, max_batch)
    n = 5
    outs = eng.generate(_requests(n, max_tokens=[4, 16, 8, 12, 4]))
    c = eng.counters()
    assert eng.page_pool_stats()["preemptions"] == 0
    assert c["lane_iters_active"] == sum(o.steps for o in outs)
    assert c["admitted_lanes"] == n
    assert 1 <= c["admit_calls"] <= n
    assert c["forwards"] == int(eng._state.calls)
    assert c["forwards"] == (c["admit_calls"] + c["steps"]
                             + c["refine_forwards"])
    assert c["lane_iters_active"] <= c["refine_forwards"] * max_batch
    if max_batch == 1:
        assert c["refine_forwards"] == c["lane_iters_active"]
        assert c["admit_calls"] == n

    # counters run on across a fresh stream(); the per-reset stats do not
    eng.generate(_requests(2, max_tokens=[4, 4]))
    c2 = eng.counters()
    assert c2["admitted_lanes"] == n + 2
    assert c2["forwards"] > c["forwards"]
    assert set(c2["host_s"]) == set(tracing.SPANS)


def test_blocks_emitted_counts_block_events(params):
    eng = _engine(params)
    events = list(eng.stream(_requests(3, max_tokens=[4, 12, 16])))
    c = eng.counters()
    assert c["blocks_emitted"] == len(events)
    assert c["steps"] >= max(e.index for e in events) + 1


def test_programs_carry_the_device_scopes(params):
    """The lowered admission and decode programs name their phases in
    their operations' ``op_name`` metadata."""
    eng = _engine(params)
    st, N = eng._state, eng.n_lanes
    run = jnp.ones((N,), bool)
    decode = eng._jit_decode_block.lower(
        params, st, run, sampled=False).as_text(debug_info=True)
    admit = eng._jit_admit.lower(
        params, st, jnp.zeros((N, P), jnp.int32), run,
        jnp.full((N,), G // B, jnp.int32), st.temps, st.taus, st.eos,
        st.keys).as_text(debug_info=True)
    for scope in (tracing.REFINE, tracing.SELECT, tracing.COMMIT):
        assert scope in decode, scope
    assert f"{tracing.REFINE}/{tracing.SELECT}" in decode
    assert tracing.PREFILL in admit
    assert tracing.PREFILL not in decode and tracing.REFINE not in admit


def test_driver_spans_and_metrics(params, tmp_path):
    """Through ``EngineDriver``: the driver's spans share the thread of the
    engine's, its phases land in the engine's clock, and ``/metrics``
    exports the counters and the host seconds per phase."""
    eng = _engine(params)
    eng.warmup()
    jax.profiler.start_trace(str(tmp_path))
    driver = EngineDriver(eng)
    try:
        time.sleep(0.2)    # the driver waits: nothing to do
        qs = [driver.submit(r.prompt, SamplingParams(max_tokens=8))[1]
              for r in _requests(3)]
        for q in qs:
            while q.get(timeout=120) is not None:
                pass
        text = driver.metrics()
    finally:
        driver.shutdown()
        jax.profiler.stop_trace()
    threads = [{e[0] for e in evs}
               for evs in _host_events(str(tmp_path)).values()]
    assert len(threads) == 1
    names = threads[0]
    assert {"cdlm.driver.wait", "cdlm.driver.lock", "cdlm.driver.route",
            "cdlm.step", "cdlm.step.sync"} <= names
    c = eng.counters()
    assert c["host_s"]["driver.wait"] > 0.05
    assert c["admitted_lanes"] == 3
    lines = text.splitlines()
    assert "# TYPE cdlm_engine_forwards_total counter" in lines
    assert any(ln.startswith("cdlm_engine_lane_iters_active_total ")
               for ln in lines)
    phases = [ln for ln in lines
              if ln.startswith("cdlm_engine_host_seconds_total{")]
    assert len(phases) == len(tracing.SPANS)
    assert any('phase="admit.keys"' in ln for ln in phases)


def test_span_times_its_phase():
    clock = tracing.PhaseClock()
    with clock.span("decode"):
        time.sleep(0.01)
    with clock.span("admit", ids=tracing.ids([3, 14])):
        pass
    assert clock.host_s["decode"] >= 0.01
    assert 0 <= clock.host_s["admit"] < clock.host_s["decode"]
    assert tracing.ids([3, 14]) == "3 14"
    with pytest.raises(KeyError):
        clock.span("nope")
