"""Serving launcher CLI: load a checkpoint (or train the cached toy assets)
and serve batched requests with any sampler strategy, under either the
static or the continuous block-level batching scheduler.

    PYTHONPATH=src python -m repro.launch.serve --sampler cdlm --requests 32
    PYTHONPATH=src python -m repro.launch.serve --scheduler continuous

With ``--http`` the engine is exposed through the stdlib HTTP frontend
(``repro.serving.server``) instead of replaying a local batch: an
OpenAI-style ``POST /v1/completions`` (SSE streaming and non-streaming),
``GET /healthz`` and ``GET /metrics``:

    PYTHONPATH=src python -m repro.launch.serve --scheduler continuous \
        --http --port 8000
"""
import argparse
import os
import sys
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sampler", default="cdlm",
                    choices=["vanilla", "fast_dllm", "dual_cache",
                             "interval_cache", "cdlm", "ar"])
    ap.add_argument("--scheduler", default="static",
                    choices=["static", "continuous"],
                    help="continuous = slot-based block-level batching "
                         "(cdlm only)")
    ap.add_argument("--cache-layout", default="dense",
                    choices=["dense", "paged"],
                    help="KV memory layout: dense per-lane buffers, or a "
                         "global page pool + per-lane page tables "
                         "(page size = block size)")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="paged layout: page-pool size in pages "
                         "(default: dense-equivalent capacity)")
    ap.add_argument("--paged-kernel", action="store_true",
                    help="paged + continuous only: decode through the "
                         "Pallas page-table flash-decode kernel instead of "
                         "the bit-exact gather path (interpret mode on CPU)")
    ap.add_argument("--fused-select", action="store_true",
                    help="fused unembed + online-softmax candidate "
                         "selection (repro.kernels.select): decode skips "
                         "the lm_head and never materializes (b, ., V) "
                         "logits; greedy decoding only")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--threshold", type=float, default=0.9)
    ap.add_argument("--ckpt", default=None,
                    help="npz checkpoint (defaults to cached bench assets)")
    ap.add_argument("--http", action="store_true",
                    help="serve over HTTP (/v1/completions with SSE "
                         "streaming, /healthz, /metrics) instead of "
                         "replaying a local request batch")
    ap.add_argument("--host", default=None,
                    help="HTTP bind host (default: ServeConfig.http_host)")
    ap.add_argument("--port", type=int, default=None,
                    help="HTTP bind port (default: ServeConfig.http_port)")
    args = ap.parse_args()
    if args.paged_kernel and (args.scheduler != "continuous"
                              or args.cache_layout != "paged"):
        ap.error("--paged-kernel requires --scheduler continuous "
                 "--cache-layout paged")

    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    "..", "..", ".."))
    from benchmarks import common
    from repro.configs.base import ServeConfig
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving import Request, efficiency_report, make_engine

    enable_compile_cache()
    if args.ckpt:
        import jax
        from repro.checkpoint import restore
        from repro.models import init_model
        params = restore(init_model(jax.random.PRNGKey(0), common.CFG),
                         args.ckpt)
    else:
        params = (common.get_student() if args.sampler == "cdlm"
                  else common.get_teacher())

    serve = ServeConfig(max_batch=args.batch,
                        block_size=common.CDLM_CFG.block_size,
                        gen_length=common.TASK.gen_len,
                        sampler=args.sampler,
                        conf_threshold=args.threshold,
                        scheduler=args.scheduler,
                        cache_layout=args.cache_layout,
                        page_pool_pages=args.pool_pages,
                        fused_select=args.fused_select)
    kw = {"use_paged_kernel": True} if args.paged_kernel else {}
    eng = make_engine(params, common.CFG, serve,
                      prompt_len=common.TASK.prompt_len, **kw)
    if args.http:
        from repro.serving.server import serve_http
        host = args.host if args.host is not None else serve.http_host
        port = args.port if args.port is not None else serve.http_port
        eng.warmup(per_request=True)
        print(f"serving /v1/completions on http://{host}:{port} "
              f"(prompt_len={common.TASK.prompt_len}, "
              f"scheduler={args.scheduler}) — Ctrl-C to stop")
        serve_http(eng, host, port)
        return
    ev = common.corpus().eval_batch(args.requests)
    reqs = [Request(prompt=p, id=i) for i, p in enumerate(ev["prompt"])]
    eng.warmup()
    t0 = time.perf_counter()
    resp = eng.generate(reqs)
    wall = time.perf_counter() - t0
    rep = efficiency_report(resp)
    # wall-clock TPS is comparable across schedulers; latency_s is not
    # (compute share for static, arrival->completion for continuous)
    tps = sum(r.gen_length for r in resp) / wall if wall else 0.0
    print(f"{args.sampler}/{args.scheduler}: TPS={tps:.0f} "
          f"latency={rep['latency_s']*1e3:.1f}ms steps={rep['steps']:.1f} "
          f"gen_len={rep['gen_length']:.1f}  ({len(resp)} requests)")
    if args.cache_layout == "paged" and args.scheduler == "continuous":
        ps = eng.page_pool_stats()
        print(f"page pool: {ps['peak_pages']:.0f}/{ps['n_pages']:.0f} pages "
              f"peak ({ps['peak_occupancy']:.0%}), "
              f"{ps['preemptions']:.0f} preemptions, "
              f"{ps['stall_rounds']:.0f} stall rounds")


if __name__ == "__main__":
    main()
