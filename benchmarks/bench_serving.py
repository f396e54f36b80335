"""Serving benchmarks: (1) static fixed-shape batching vs continuous
block-level batching on a Poisson arrival trace with mixed generation
lengths, and (2) dense vs block-paged KV layouts at a fixed page-pool
memory budget.

Static batching pads requests into fixed chunks and runs each chunk to
completion: a lane capped at one block still rides along for the full
block grid, and a chunk cannot launch until its last request has arrived.
The continuous engine evicts finished lanes at every block boundary and
admits queued requests into the freed cache rows mid-flight, so short
requests release their lanes early and the decode batch stays full.

The layout face-off fixes the KV byte budget: the dense engine gets
``budget_pages // pages_per_canvas`` lanes (every lane preallocates the
whole canvas), while the paged engine gets the same budget as a shared
page pool and more lanes — short requests only consume the pages they
commit, so the pool sustains more concurrent decodes per HBM byte.

    PYTHONPATH=src python -m benchmarks.bench_serving
    PYTHONPATH=src python -m benchmarks.bench_serving --cache-layout paged
    PYTHONPATH=src python -m benchmarks.bench_serving --smoke \
        --json BENCH_serving.json
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from benchmarks import common
from repro.configs.base import ServeConfig
from repro.launch.compile_cache import enable_compile_cache


def _run_static_trace(eng, reqs, max_batch):
    """Replay the trace through the static engine: chunks form in arrival
    order and launch once every member has arrived."""
    by_id = {r.id: r for r in reqs}
    lat = {}
    out = []
    t0 = time.perf_counter()
    for i in range(0, len(reqs), max_batch):
        chunk = reqs[i:i + max_batch]
        ready_at = max(r.arrival_s for r in chunk)
        now = time.perf_counter() - t0
        if ready_at > now:
            time.sleep(ready_at - now)
        rs = eng.generate(chunk)
        done = time.perf_counter() - t0
        for r in rs:
            lat[r.id] = done - by_id[r.id].arrival_s
        out.extend(rs)
    return out, lat, time.perf_counter() - t0


def _report(name, resp, lat_by_id, makespan):
    toks = sum(r.gen_length for r in resp)
    lats = np.asarray(sorted(lat_by_id.values()))
    tps = toks / makespan if makespan > 0 else float("inf")
    print(f"{name:12s} {tps:>9.0f} {makespan*1e3:>10.1f} "
          f"{np.median(lats)*1e3:>9.1f} {lats[int(0.95*(len(lats)-1))]*1e3:>9.1f} "
          f"{toks:>7d}")
    return tps


def _kv_page_bytes():
    """KV bytes of one pool page (all attention slots, K+V)."""
    import jax

    from repro.core import cache as C
    T = common.TASK.prompt_len + common.TASK.gen_len
    paged = jax.eval_shape(lambda: C.init_paged_cache(
        common.CFG, 1, T, n_pages=1,
        page_size=common.CDLM_CFG.block_size, dtype=common.CFG.dtype))
    return sum(leaf.size * leaf.dtype.itemsize
               for slot in paged.slots for k, leaf in slot.items()
               if k in ("k", "v"))


def run_schedulers(params, csv_rows=None, results=None, n_requests=96,
                   max_batch=4, rate_hz=1000.0, sampled_frac=0.0):
    """Static vs continuous scheduling (dense layout). ``sampled_frac``
    mixes per-request sampled lanes (temperature 0.7) into the trace —
    both schedulers serve them through the per-lane params path."""
    from repro.serving import ContinuousEngine, Engine

    reqs = common.poisson_trace(n=n_requests, rate_hz=rate_hz, seed=0,
                                sampled_frac=sampled_frac)
    kw = dict(block_size=common.CDLM_CFG.block_size,
              gen_length=common.TASK.gen_len, sampler="cdlm",
              conf_threshold=0.9, max_batch=max_batch)

    static_eng = Engine(params, common.CFG,
                        ServeConfig(scheduler="static", **kw),
                        prompt_len=common.TASK.prompt_len)
    cont_eng = ContinuousEngine(params, common.CFG,
                                ServeConfig(scheduler="continuous", **kw),
                                prompt_len=common.TASK.prompt_len)
    # sampled traces hit the per-lane jit variants: precompile them so
    # the timed region measures scheduling, not one-off compiles
    static_eng.warmup(per_request=sampled_frac > 0)
    cont_eng.warmup(per_request=sampled_frac > 0)

    mix = (f", {sampled_frac:.0%} sampled lanes" if sampled_frac else "")
    print(f"\n== serving schedulers ({n_requests} reqs, Poisson "
          f"{rate_hz:.0f}/s, batch {max_batch}, mixed max_tokens{mix}) ==")
    print(f"{'scheduler':12s} {'tok/s':>9} {'makespan':>10} {'p50 lat':>9} "
          f"{'p95 lat':>9} {'tokens':>7}")

    s_resp, s_lat, s_make = _run_static_trace(static_eng, reqs, max_batch)
    s_tps = _report("static", s_resp, s_lat, s_make)

    t0 = time.perf_counter()
    c_resp = cont_eng.generate(reqs)
    c_make = time.perf_counter() - t0
    c_lat = {r.id: r.latency_s for r in c_resp}
    c_tps = _report("continuous", c_resp, c_lat, c_make)

    assert len(c_resp) == len(s_resp) == n_requests
    speedup = c_tps / s_tps if s_tps else float("inf")
    verdict = "OK" if c_tps >= s_tps else "REGRESSION"
    print(f"continuous/static throughput: x{speedup:.2f}  [{verdict}]")

    if csv_rows is not None:
        csv_rows.append(("serving/static_tps", s_make * 1e6 / n_requests,
                         f"{s_tps:.0f}"))
        csv_rows.append(("serving/continuous_tps", c_make * 1e6 / n_requests,
                         f"{c_tps:.0f}"))
        csv_rows.append(("serving/speedup", 0.0, f"{speedup:.2f}"))
    if results is not None:
        results["schedulers"] = {
            "n_requests": n_requests, "max_batch": max_batch,
            "static_tps": s_tps, "continuous_tps": c_tps,
            "speedup": speedup,
        }
        shape = {"n_requests": n_requests, "max_batch": max_batch}
        results.setdefault("records", []).extend([
            common.record("serving_sched", shape, "tok_per_s", s_tps,
                          config={"scheduler": "static"}),
            common.record("serving_sched", shape, "tok_per_s", c_tps,
                          config={"scheduler": "continuous"}),
            common.record("serving_sched", shape,
                          "continuous_static_speedup", speedup),
        ])
    return speedup


def run_layouts(params, csv_rows=None, results=None, n_requests=64,
                rate_hz=1000.0, budget_pages=12, paged_lanes=None):
    """Dense vs paged KV layout at the same page-pool memory budget.

    The dense engine's lane count is what the budget can preallocate
    (whole canvases); the paged engine shares the identical budget as a
    pool and admits by free pages, so mixed-length traffic packs more
    concurrent lanes into the same bytes.
    """
    from repro.serving import ContinuousEngine

    B = common.CDLM_CFG.block_size
    P, G = common.TASK.prompt_len, common.TASK.gen_len
    n_tables = -(-(P + G) // B)
    dense_lanes = max(1, budget_pages // n_tables)
    paged_lanes = paged_lanes or 2 * dense_lanes
    page_mb = _kv_page_bytes() / 1e6
    reqs = common.poisson_trace(n=n_requests, rate_hz=rate_hz, seed=1)

    kw = dict(block_size=B, gen_length=G, sampler="cdlm",
              conf_threshold=0.9, scheduler="continuous")
    dense_eng = ContinuousEngine(
        params, common.CFG,
        ServeConfig(max_batch=dense_lanes, **kw), prompt_len=P)
    paged_eng = ContinuousEngine(
        params, common.CFG,
        ServeConfig(max_batch=paged_lanes, cache_layout="paged",
                    page_pool_pages=budget_pages, **kw), prompt_len=P)
    dense_eng.warmup()
    paged_eng.warmup()

    print(f"\n== cache layouts at fixed budget ({budget_pages} pages = "
          f"{budget_pages * page_mb:.2f} MB KV; {n_requests} reqs, mixed "
          f"max_tokens; dense {dense_lanes} lanes, paged {paged_lanes} "
          "lanes) ==")
    print(f"{'layout':12s} {'tok/s':>9} {'makespan':>10} {'peak lanes':>10} "
          f"{'avg lanes':>10} {'pool peak':>9}")

    rows = {}
    for name, eng in (("dense", dense_eng), ("paged", paged_eng)):
        t0 = time.perf_counter()
        resp = eng.generate(reqs)
        make = time.perf_counter() - t0
        assert len(resp) == n_requests
        toks = sum(r.gen_length for r in resp)
        tps = toks / make if make > 0 else float("inf")
        conc = eng.concurrency_stats()
        pool = eng.page_pool_stats()
        occ = (f"{pool['peak_occupancy']:.0%}" if name == "paged" else "-")
        print(f"{name:12s} {tps:>9.0f} {make*1e3:>10.1f} "
              f"{conc['peak_lanes']:>10.0f} {conc['avg_lanes']:>10.2f} "
              f"{occ:>9}")
        rows[name] = {"tps": tps, "makespan_s": make, **conc,
                      **({"pool": pool} if name == "paged" else {})}

    gain = rows["paged"]["peak_lanes"] / max(rows["dense"]["peak_lanes"], 1)
    verdict = ("OK" if rows["paged"]["peak_lanes"]
               >= rows["dense"]["peak_lanes"] else "REGRESSION")
    print(f"paged/dense peak concurrency at fixed memory: x{gain:.2f}  "
          f"[{verdict}]")

    if csv_rows is not None:
        csv_rows.append(("serving/dense_peak_lanes", 0.0,
                         f"{rows['dense']['peak_lanes']:.0f}"))
        csv_rows.append(("serving/paged_peak_lanes", 0.0,
                         f"{rows['paged']['peak_lanes']:.0f}"))
        csv_rows.append(("serving/paged_concurrency_gain", 0.0,
                         f"{gain:.2f}"))
    if results is not None:
        results["layouts"] = {
            "budget_pages": budget_pages, "page_mb": page_mb,
            "dense_lanes": dense_lanes, "paged_lanes": paged_lanes,
            "concurrency_gain": gain, **rows,
        }
        shape = {"n_requests": n_requests, "budget_pages": budget_pages,
                 "dense_lanes": dense_lanes, "paged_lanes": paged_lanes}
        pool = rows["paged"]["pool"]
        results.setdefault("records", []).extend([
            common.record("serving_layout", shape, "tok_per_s",
                          rows["dense"]["tps"], config={"layout": "dense"}),
            common.record("serving_layout", shape, "tok_per_s",
                          rows["paged"]["tps"], config={"layout": "paged"}),
            common.record("serving_layout", shape, "paged_dense_tps_ratio",
                          rows["paged"]["tps"]
                          / max(rows["dense"]["tps"], 1e-9)),
            common.record("serving_layout", shape, "concurrency_gain", gain),
            common.record("serving_layout", shape, "stall_rounds",
                          pool["stall_rounds"], config={"layout": "paged"}),
            common.record("serving_layout", shape, "preemptions",
                          pool["preemptions"], config={"layout": "paged"}),
        ])
    return gain


def run(csv_rows=None, n_requests=96, max_batch=4, rate_hz=1000.0,
        results=None, params=None, layouts=True, budget_pages=12,
        sampled_frac=0.0):
    params = params if params is not None else common.get_student()
    speedup = run_schedulers(params, csv_rows=csv_rows, results=results,
                             n_requests=n_requests, max_batch=max_batch,
                             rate_hz=rate_hz, sampled_frac=sampled_frac)
    if layouts:
        run_layouts(params, csv_rows=csv_rows, results=results,
                    n_requests=max(8, n_requests * 2 // 3), rate_hz=rate_hz,
                    budget_pages=budget_pages)
    return speedup


def main(argv=None):
    ap = common.make_parser(
        description=__doc__,
        smoke_help="random-init params (no cached training assets) and a "
                   "short trace — CI-sized; scheduling and layout behavior "
                   "are model-quality independent")
    ap.add_argument("--cache-layout", default="both",
                    choices=["dense", "paged", "both"],
                    help="'dense' skips the layout face-off; 'paged'/'both' "
                         "run dense-vs-paged at a fixed page budget")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--budget-pages", type=int, default=12)
    ap.add_argument("--sampled-frac", type=float, default=0.0,
                    help="share of trace requests carrying per-request "
                         "SamplingParams (temperature 0.7, own seed) — "
                         "exercises mixed greedy/sampled batches")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.smoke:
        import jax

        from repro.models import init_model
        params = init_model(jax.random.PRNGKey(0), common.CFG)
        n_requests = args.requests or 16
    else:
        params = common.get_student()
        n_requests = args.requests or 96

    results = {"smoke": args.smoke, "n_requests": n_requests,
               "sampled_frac": args.sampled_frac, "records": []}
    run(results=results, params=params, n_requests=n_requests,
        layouts=args.cache_layout in ("paged", "both"),
        budget_pages=args.budget_pages, sampled_frac=args.sampled_frac)
    common.write_results(args.json, results)


if __name__ == "__main__":
    main()
