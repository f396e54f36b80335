"""One benchmark run of one cell: build the served engine from a
configuration file, drive it with a traffic mix through ``EngineDriver``
(the loop the HTTP handlers feed), measure a window, check the served
tokens against the plain reference, and reduce everything to metrics.

Everything a configuration, a mix or a per-layer metric owns lives in its
own file (``configs/``, ``traffic/``, ``metrics/``), found by name; what
depends on the decoder layer lives in the configuration's model family
(``families/``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from bench import families, spec, traffic
from bench import trace as tr
from bench.weights import make_weights, to_program_layout

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
DRAIN_S = 60.0  # how long a request due in the window may take after it
# open loop: client threads, each with at most one request in flight; a
# mix whose requests in flight can outnumber them sets its own ``clients``
OPEN_CLIENTS = 64


# ---------------------------------------------------------------------------
# records
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Req:
    i: int
    prompt: np.ndarray
    max_tokens: int
    tau: float                 # the request's confidence threshold
    due: float                 # scheduled send (perf_counter seconds)
    sent: float = 0.0
    rid: int = -1
    blocks: list = dataclasses.field(default_factory=list)  # (t, index, toks)
    output: object = None      # GenerationOutput of the final block
    failed: bool = False
    done: threading.Event = dataclasses.field(default_factory=threading.Event)


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    blocks: list               # [(request id, block index, block start)]
    cpu: float = 0.0           # the driver thread's CPU seconds in it


class StepRecorder:
    """Thin wrapper set on the engine instance around ``step``: records
    each step's start, end and the blocks it returned, and (when tracing)
    marks it with a ``bench.step.<n>`` host span."""

    def __init__(self, engine):
        self.steps: List[Step] = []
        self.annotate = False
        inner = engine.step

        def step():
            n = len(self.steps)
            ctx = (_annotation(f"bench.step.{n}") if self.annotate
                   else contextlib.nullcontext())
            t0, c0 = time.perf_counter(), time.thread_time()
            with ctx:
                events = inner()
            self.steps.append(Step(t0, time.perf_counter(), [
                (e.request_id, e.index, e.start) for e in events],
                time.thread_time() - c0))
            return events

        engine.step = step


def _annotation(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


# ---------------------------------------------------------------------------
# the cell
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    bench: dict
    limits: dict               # correctness limits, bench/limits/<cell>.json

    @classmethod
    def load(cls, bench_json: str, workload: str) -> "Cell":
        b = spec.load(bench_json)
        w = spec.workload(b, workload)
        import json
        with open(os.path.join(BENCH_DIR, "configs",
                               w["config"] + ".json")) as f:
            config = json.load(f)
        with open(os.path.join(BENCH_DIR, "limits", workload + ".json")) as f:
            limits = json.load(f)
        return cls(workload, config, traffic.load(BENCH_DIR, w["traffic"]),
                   b, limits)

    @property
    def family(self):
        """The configuration's model family (``bench/families/``)."""
        return families.of(self.config)

    @property
    def model_keys(self) -> tuple:
        return spec.MODEL_KEYS + self.family.KEYS

    @property
    def model(self) -> dict:
        m = {k: self.config[k] for k in self.model_keys}
        if "family" in self.config:
            m["family"] = self.config["family"]
        return m

    @property
    def serve(self) -> dict:
        return self.config["serve"]


def program_config(cell: Cell):
    """The program's ``ModelConfig`` for the cell, refused where it
    disagrees with the configuration file."""
    import dataclasses as dc
    from repro.configs.registry import get_config
    cfg = dc.replace(get_config(cell.config["arch"]),
                     **cell.config["overrides"])
    for k in cell.model_keys:
        v = cell.config[k]
        if getattr(cfg, k) != v:
            raise ValueError(f"program config {cfg.name}: {k}="
                             f"{getattr(cfg, k)!r}, configuration file says "
                             f"{v!r}")
    return cfg


def build_engine(cell: Cell, params, cfg):
    from repro.configs.base import ServeConfig
    from repro.serving import make_engine
    s = cell.serve
    serve = ServeConfig(max_batch=s["max_batch"], block_size=s["block_size"],
                        gen_length=s["gen_length"],
                        conf_threshold=cell.mix["conf_threshold"],
                        sampler="cdlm", scheduler="continuous",
                        cache_layout="paged", fused_select=True)
    return make_engine(params, cfg, serve, prompt_len=s["prompt_len"],
                       use_paged_kernel=True)


def kernel_check(engine, params) -> List[str]:
    """Names of the Pallas kernels the decode step runs; refuses a step
    without the select and paged-attention kernels or with an interpreted
    one."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import pallas_calls
    run = jnp.ones((engine.n_lanes,), bool)
    calls = pallas_calls(jax.make_jaxpr(
        lambda p, s, r: engine._decode_block(p, s, r, sampled=False))(
            params, engine._state, run))
    names = sorted({n for n, _ in calls})
    if not {"_select_kernel", "_paged_decode_kernel"} <= set(names):
        raise RuntimeError(f"decode step runs kernels {names}, not the "
                           "fused select and paged decode attention")
    if any(interp for _, interp in calls):
        raise RuntimeError("a kernel of the decode step runs interpreted")
    return names


# ---------------------------------------------------------------------------
# load generation
# ---------------------------------------------------------------------------
class Load:
    """Drives ``driver.submit`` with the cell's mix from a fixed set of
    client threads started with the load. Each client sends a request and
    times each ``BlockEvent`` as it comes off the request's queue; it
    sends its next one when the last has finished (closed loop), or takes
    the next arrival of the schedule that no client has taken and sends it
    when it is due (open loop). A request is timed from when it was due,
    so a late send counts against it."""

    def __init__(self, cell: Cell, driver, seed: int, seconds: float):
        from repro.serving.api import SamplingParams
        self.cell, self.driver, self.seed = cell, driver, seed
        self.SamplingParams = SamplingParams
        if cell.mix["loop"] == "open":
            self.times, self.lengths = traffic.open_schedule(
                cell.mix, seed, seconds)
        else:
            self.lengths = traffic.lengths(cell.mix, seed)
        self.reqs: List[Req] = []
        self.stop = threading.Event()
        self.lock = threading.Lock()
        self.threads: List[threading.Thread] = []
        self.next = 0              # open loop: the next arrival untaken
        self.lateness: List[tuple] = []   # (due, seconds sent after it)

    def _new(self, due: float, i: Optional[int] = None) -> Req:
        with self.lock:
            i = len(self.reqs) if i is None else i
            s = self.cell.serve
            r = Req(i, traffic.prompt(self.seed, i, s["prompt_len"],
                                      self.cell.config["prompt_id_max"]),
                    int(self.lengths[i % len(self.lengths)]),
                    traffic.tau(self.cell.mix, i), due)
            self.reqs.append(r)
        return r

    def _send(self, r: Req) -> None:
        params = self.SamplingParams(conf_threshold=r.tau,
                                     max_tokens=r.max_tokens)
        r.sent = time.perf_counter()
        try:
            r.rid, q = self.driver.submit(r.prompt, params)
        except Exception:  # noqa: BLE001 — a refused request is a failure
            r.failed = True
            r.done.set()
            return
        while True:
            ev = q.get()
            if ev is None:
                break
            r.blocks.append((time.perf_counter(), ev.index, ev.tokens))
            if ev.finished:
                r.output = ev.output
        if r.output is None:
            r.failed = True
        r.done.set()

    def _closed_client(self, t0: float) -> None:
        while not self.stop.is_set():
            r = self._new(time.perf_counter())
            self._send(r)

    def _open_client(self, t0: float) -> None:
        while True:
            with self.lock:
                i, self.next = self.next, self.next + 1
            if i >= len(self.times):
                return
            due = t0 + float(self.times[i])
            if self.stop.wait(max(0.0, due - time.perf_counter())):
                return
            r = self._new(due, i)
            self.lateness.append((due, time.perf_counter() - due))
            self._send(r)

    def start(self) -> float:
        closed = self.cell.mix["loop"] == "closed"
        target = self._closed_client if closed else self._open_client
        n = self.cell.mix.get("clients", OPEN_CLIENTS)
        t0 = time.perf_counter()
        for _ in range(n):
            t = threading.Thread(target=target, args=(t0,), daemon=True)
            t.start()
            self.threads.append(t)
        return t0

    def finish(self, deadline: float) -> None:
        """Stop sending; wait for the requests in flight until
        ``deadline``."""
        self.stop.set()
        for r in list(self.reqs):
            r.done.wait(max(0.0, deadline - time.perf_counter()))
        for t in list(self.threads):
            t.join(max(0.0, deadline - time.perf_counter()))


# ---------------------------------------------------------------------------
# end-to-end metrics
# ---------------------------------------------------------------------------
def delivered(r: Req, start: int, block: int) -> int:
    """Tokens of a block that the request asked for."""
    return max(0, min(block, r.max_tokens - start))


def end_to_end(reqs: List[Req], ws: float, we: float, block: int) -> dict:
    """``tok_s``, ``gap_p95_ms`` and ``ttfb_p95_ms`` of the window
    ``[ws, we)``, with the sample counts."""
    toks, gaps, ttfb = 0, [], []
    for r in reqs:
        prev = None
        for t, idx, _ in r.blocks:
            if ws <= t < we:
                toks += delivered(r, idx * block, block)
                if prev is not None:
                    gaps.append(t - prev)
            prev = t
        if ws <= r.due < we:
            first = r.blocks[0][0] if r.blocks else None
            ttfb.append((first if first is not None and first < we else we)
                        - r.due)
    out = {"tok_s": toks / (we - ws), "n_gaps": len(gaps),
           "n_due": len(ttfb)}
    if gaps:
        out["gap_p95_ms"] = float(np.percentile(gaps, 95)) * 1e3
    if ttfb:
        out["ttfb_p95_ms"] = float(np.percentile(ttfb, 95)) * 1e3
    return out


# ---------------------------------------------------------------------------
# per-layer metrics
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class RunView:
    """What a per-layer reader may read."""
    cell: Cell
    reqs: List[Req]
    steps: List[Step]          # steps that started inside the window
    all_steps: List[Step]      # every step, indexed as its trace span
    ws: float
    we: float
    pool: Dict[str, float]     # engine.page_pool_stats()
    peaks: dict
    trace: Optional[dict]      # bench.trace.reduce() of the traced part


def load_reader(name: str):
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    sp = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read


def per_layer(cell: Cell, view: RunView) -> dict:
    out = {}
    for m in spec.cell_metrics(cell.bench, cell.name, "per_layer"):
        v = load_reader(m["name"])(view)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------
def sample(reqs: List[Req], ws: float, we: float, n: int, seed: int):
    """Finished requests that got a block in the window, drawn from the
    seed: up to ``n`` of those with tau = 0, whose every token is read,
    with the longest of them, and up to ``n // 2`` of the others."""
    pool = [r for r in reqs if r.output is not None
            and any(ws <= t < we for t, _, _ in r.blocks)]
    rng = np.random.default_rng([seed, 4])
    picked = []
    for part, k in (([r for r in pool if r.tau == 0.0], n),
                    ([r for r in pool if r.tau != 0.0], n // 2)):
        if not part or k == 0:
            continue
        longest = max(part, key=lambda r: (len(r.blocks), -r.i))
        rest = [r for r in part if r is not longest]
        pick = rng.choice(len(rest), min(k - 1, len(rest)), replace=False)
        picked += [longest] + [rest[j] for j in sorted(pick)]
    return picked


def check(cell: Cell, weights, picked: List[Req], control: bool = False,
          slack: Optional[float] = None):
    """Reference replay of the sampled requests' served tokens, at the
    cell's order slack unless ``slack`` is given."""
    from bench import reference
    s = cell.serve
    B, G = s["block_size"], s["gen_length"]
    served = np.zeros((len(picked), G), np.int32)
    n_blocks = np.zeros((len(picked),), np.int64)
    for j, r in enumerate(picked):
        for _, idx, toks in r.blocks:
            served[j, idx * B:(idx + 1) * B] = toks
        n_blocks[j] = len(r.blocks)
    taus = np.asarray([r.tau for r in picked], np.float32)
    return reference.replay(cell.model, weights,
                            np.stack([r.prompt for r in picked]), served,
                            n_blocks, taus, block_size=B, control=control,
                            slack=cell.limits["slack"] if slack is None
                            else slack)


def judge(failed: int, gap: float, tokens: int, limits: dict) -> bool:
    """``correct``: every request due in the window finished, the widest
    logit gap is within its limit, and enough served tokens were read."""
    return (failed == 0 and gap <= limits["logit_gap"]
            and tokens >= limits["min_tokens"])


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------
def device_info() -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found {devs[0].platform!r} "
                         f"({devs[0].device_kind}); there is no fallback")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class CompileCounter:
    """Counts programs built while the process runs: ``n`` in all (from
    the persistent cache or compiled), ``hits`` found in the persistent
    cache, ``window`` compiled while ``on`` is set (there should be none),
    ``window_hits`` loaded from the cache and ``window_traces`` functions
    traced while it is set."""

    def __init__(self):
        import jax
        self.on, self.n, self.hits, self.window = False, 0, 0, 0
        self.window_hits, self.window_traces = 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._built)
        jax.monitoring.register_event_listener(self._hit)

    def _built(self, event, duration, **kw):
        if event.endswith("backend_compile_duration"):
            self.n += 1
            self.window += self.on
        elif event.endswith("jaxpr_trace_duration"):
            self.window_traces += self.on

    def _hit(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
            self.window_hits += self.on


class GcPauses:
    """Garbage-collector pauses while ``on`` is set: (generation,
    seconds)."""

    def __init__(self):
        self.on, self.pauses, self._t = False, [], 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self.on:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def close(self):
        gc.callbacks.remove(self._cb)


@dataclasses.dataclass
class Served:
    """A warmed engine behind its driver, and what set-up made."""
    dev: dict
    peaks: Optional[dict]
    weights: dict
    engine: object
    recorder: StepRecorder
    driver: object
    setup_s: float
    compiles: CompileCounter


def serve(cell: Cell, seed: int, t_start: float, *, require_tpu: bool = True,
          faults=None) -> Served:
    """Set-up: device check, compile cache, weights from the seed, the
    engine warmed on the cell's shapes, its kernels checked, the driver
    started. ``t_start`` is the process start on the host clock."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving.server import EngineDriver

    dev = device_info() if require_tpu else {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind, "count": len(jax.devices())}
    chips = spec.workload(cell.bench, cell.name)["chips"]
    if dev["count"] < chips:
        raise SystemExit(f"bench: cell {cell.name} needs {chips} chips, "
                         f"JAX found {dev['count']}")
    from bench.peaks import peaks
    pk = peaks(dev["kind"]) if require_tpu else None
    if require_tpu:
        # the command's runs share one compile cache in the checkout, and
        # keep every program in it; tests leave JAX's settings alone
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = CompileCounter()  # jax's import compiles nothing

    cfg = program_config(cell)
    weights = make_weights(cell.model, seed)
    params = to_program_layout(weights, cell.model)
    engine = build_engine(cell, params, cfg)
    engine.warmup()
    if require_tpu:
        kernel_check(engine, params)
    recorder = StepRecorder(engine)
    if faults is not None:
        faults(engine)
    driver = EngineDriver(engine)
    # what set-up made lives as long as the engine: keep it out of the
    # collector's scans, so that a full collection in the window walks
    # only what the window itself allocates
    gc.collect()
    gc.freeze()
    return Served(dev, pk, weights, engine, recorder, driver,
                  time.perf_counter() - t_start, compiles)


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        *, require_tpu: bool = True, keep_trace: Optional[str] = None,
        faults=None, control: bool = False) -> dict:
    """One run. ``faults`` (tests) is called with the engine before the
    window, to break the timed path underneath. ``control`` also reads
    the float8 control's gap at the same positions and judges it as the
    program is judged (``bench/control.py``). ``keep_trace`` also writes
    the reduced trace there (``tests/bench/record_trace.py``)."""
    import jax
    sv = serve(cell, seed, t_start, require_tpu=require_tpu, faults=faults)
    dev, pk, weights, engine = sv.dev, sv.peaks, sv.weights, sv.engine
    recorder, driver, setup_s, compiles = (sv.recorder, sv.driver,
                                           sv.setup_s, sv.compiles)
    del sv
    setup_compiles = compiles.n - compiles.hits

    pauses = GcPauses()
    load = Load(cell, driver, seed, seconds)
    t0 = load.start()
    ws = t0 + cell.mix.get("lead_in_s", 0)
    time.sleep(max(0.0, ws - time.perf_counter()))
    while (len(recorder.steps) < cell.mix.get("lead_in_steps", 0)
           and driver.healthy):
        time.sleep(0.01)
    ws = max(ws, time.perf_counter())
    compiles.on = pauses.on = True
    trace_info, log_dir = None, None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        traced = min(seconds, cell.config["trace_seconds"])
        recorder.annotate = True
        jax.profiler.start_trace(log_dir)
        with _annotation("bench.window"):
            time.sleep(traced)
        jax.profiler.stop_trace()
        recorder.annotate = False
    we = ws + seconds
    time.sleep(max(0.0, we - time.perf_counter()))
    compiles.on = pauses.on = False
    pauses.close()
    load.finish(we + DRAIN_S)
    stats = jax.devices()[0].memory_stats() or {}
    pool = engine.page_pool_stats()
    driver.shutdown()

    if trace:
        compact = tr.read_xspace(tr.find_xspace(log_dir))
        if keep_trace:
            tr.save(compact, keep_trace)
        shutil.rmtree(log_dir, ignore_errors=True)
        trace_info = tr.reduce(compact,
                               kernels=getattr(cell.family, "KERNELS", None))

    reqs = load.reqs
    steps = [s for s in recorder.steps if ws <= s.t0 < we]
    e2e = end_to_end(reqs, ws, we, cell.serve["block_size"])
    due = [r for r in reqs if ws <= r.due < we]
    # a request that never finished, or was refused, failed
    failed = sum(r.failed or not r.done.is_set() for r in due)
    view = RunView(cell, reqs, steps, recorder.steps, ws, we, pool, pk,
                   trace_info)
    picked = sample(reqs, ws, we, cell.config["check_requests"], seed)

    # free the program's state before the reference runs on the chip
    del driver, engine, recorder
    gc.unfreeze()
    gc.collect()
    limits = cell.limits
    t_check = time.perf_counter()
    res = (check(cell, weights, picked, control) if picked
           else {"gap": float("inf"), "tokens": 0})
    check_s = time.perf_counter() - t_check
    checks = {
        # the widest gap of a served token's logit below the reference's
        # best, and how many served tokens were compared
        "logit_gap": {"value": res["gap"], "limit": limits["logit_gap"]},
        "tokens": {"value": res["tokens"], "limit": limits["min_tokens"]},
    }
    correct = judge(failed, res["gap"], res["tokens"], limits)

    if trace:
        metrics = per_layer(cell, view)
    else:
        metrics = {}
        for m in spec.cell_metrics(cell.bench, cell.name, "end_to_end"):
            # a metric split by cells (``gap_p95_ms.poisson``) is read as
            # its base quantity
            base = m["name"].split(".")[0]
            v = setup_s if base == "setup_s" else e2e.get(base)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = dict(dev, memory_peak_bytes=int(stats.get("peak_bytes_in_use",
                                                       0)))
    result = {"correct": bool(correct), "attempted": len(due),
              "failed": int(failed), "metrics": metrics,
              "device": device}
    if trace_info is not None:
        device["busy_s"] = trace_info["busy_s"]
        device["window_s"] = trace_info["window_s"]
        result["breakdown"] = {"device_ops": trace_info["device_ops"],
                               "idle_gaps": trace_info["idle_gaps"]}
    late = max(load.lateness, key=lambda x: x[1], default=(ws, 0.0))
    diag = {"setup_s": setup_s, "lead_in_s": ws - t0,
            "requests": len(reqs), "due": len(due),
            "steps_in_window": len(steps),
            "compiles_in_setup": setup_compiles,
            "compiles_in_window": compiles.window,
            "cache_hits_in_window": compiles.window_hits,
            "traces_in_window": compiles.window_traces,
            "gaps": e2e["n_gaps"], "sampled_requests": len(picked),
            "check_s": check_s, "blocks_read": len(res.get("blocks", [])),
            "tau_reads_by_step": res.get("tau_reads_by_step"),
            "sender_late_max_ms": late[1] * 1e3,
            "sender_late_max_at_s": late[0] - ws,
            "step_max_ms": max((s.t1 - s.t0 for s in steps), default=0) * 1e3,
            "step_max_cpu_ms": max(steps, key=lambda s: s.t1 - s.t0).cpu * 1e3
            if steps else 0.0,
            "gc_in_window": {
                "n": len(pauses.pauses),
                "gen2": sum(g == 2 for g, _ in pauses.pauses),
                "max_ms": max((d for _, d in pauses.pauses), default=0) * 1e3},
            "e2e": e2e}
    if control:
        diag["control_gap"] = res["control_gap"]
        diag["control_correct"] = judge(failed, res["control_gap"],
                                        res["tokens"], limits)
    result["checks"] = checks
    return {"result": result, "diag": diag, "view": view, "picked": picked,
            "weights": weights}


def add_paths():
    """``src/`` and the checkout root on ``sys.path`` (the command runs
    as ``python3 bench/run.py`` from the root)."""
    root = os.path.dirname(BENCH_DIR)
    for p in (os.path.join(root, "src"), root):
        if p not in sys.path:
            sys.path.insert(0, p)

