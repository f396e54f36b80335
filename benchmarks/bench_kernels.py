"""Kernel-layer microbenchmarks.

Attention: jit'd pure-jnp oracle vs the chunked flash path at model shapes
(the Pallas kernels themselves are validated in interpret mode — timing
them on CPU would measure the interpreter).

Select: the decode loop's per-step vocabulary cost. Baseline = dense
candidate selection (lm_head logits + fp32 softmax + argmax + gather, the
(T, V) round-trip ``repro.core.diffusion.confidence_and_candidates``
performs); fused = ``repro.kernels.select`` with **no explicit knobs** —
exactly what the serving decode loop calls — so the timed path is the
jit-compiled impl/tile the tuned-config registry
(``repro.kernels.tuning``) resolves for this backend and vocab bucket.
Swept at Dream/LLaDA-scale vocabs (V ∈ {32k, 128k}), where the baseline's
(T, V) HBM round-trip dominates a cached decode step.

``--tune`` re-runs the registry's config sweep first and persists the
winners to ``src/repro/kernels/tuned_configs.json`` (the checked-in
table), then benches with the freshly tuned configs.

    PYTHONPATH=src python -m benchmarks.bench_kernels
    PYTHONPATH=src python -m benchmarks.bench_kernels --tune
    PYTHONPATH=src python -m benchmarks.bench_kernels --smoke \
        --json BENCH_kernels.json
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from benchmarks import common
from repro.core import masks
from repro.kernels import tuning
from repro.kernels.select import fused_select, select_ref
from repro.launch.compile_cache import enable_compile_cache
from repro.models.layers import attention_core

SELECT_VOCABS = (32_768, 131_072)


def _time(fn, *args, iters=5, repeats=3):
    """Best-of-``repeats`` average over ``iters`` calls — min-of-windows
    rejects scheduler/load noise that a single average folds in."""
    out = fn(*args)
    (out[0] if isinstance(out, tuple) else out).block_until_ready()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        (out[0] if isinstance(out, tuple) else out).block_until_ready()
        best = min(best, (time.perf_counter() - t0) / iters * 1e6)
    return best


def run_attention(csv_rows=None, smoke=False, records=None):
    print("\n== kernel-layer microbench: attention (CPU, jnp paths) ==")
    key = jax.random.PRNGKey(0)
    b, Kv, G, hd = 1, 2, 4, 64
    for L in ((512,) if smoke else (512, 2048)):
        q = jax.random.normal(key, (b, L, Kv, G, hd))
        k = jax.random.normal(key, (b, L, Kv, hd))
        v = jax.random.normal(key, (b, L, Kv, hd))
        pos = jnp.arange(L)
        bf = masks.make_bias_fn(mode="block_causal", prompt_len=64,
                                block_size=32)
        bfv = lambda qp, kp, val: bf(qp, kp)
        dense = jax.jit(lambda q, k, v: attention_core(
            q, k, v, q_pos=pos, kv_pos=pos, bias_fn=bfv, scale=0.125,
            impl="dense"))
        chunk = jax.jit(lambda q, k, v: attention_core(
            q, k, v, q_pos=pos, kv_pos=pos, bias_fn=bfv, scale=0.125,
            impl="chunked", chunk=512))
        td = _time(dense, q, k, v)
        tc = _time(chunk, q, k, v)
        print(f"  block-causal attn L={L:5d}: dense={td:9.0f}us "
              f"chunked={tc:9.0f}us")
        if csv_rows is not None:
            csv_rows.append((f"kernels/attn_dense_L{L}", td, ""))
            csv_rows.append((f"kernels/attn_chunked_L{L}", tc, ""))
        if records is not None:
            shape = {"L": L, "b": b, "Kv": Kv, "G": G, "hd": hd}
            records.append(common.record(
                "attn", shape, "us_per_call", tc,
                config={"impl": "chunked", "chunk": 512}))
            records.append(common.record(
                "attn", shape, "us_per_call", td, config={"impl": "dense"}))
    return csv_rows


def run_select(csv_rows=None, results=None, smoke=False, records=None):
    """Fused-vs-baseline candidate selection at decode-step shapes.

    The fused call passes no knobs, so the timed config is whatever the
    tuned registry resolves — the number this prints is the number the
    serving decode loop gets."""
    T, d = (32, 128) if smoke else (128, 512)
    iters = 3 if smoke else 5
    print(f"\n== kernel-layer microbench: fused select "
          f"(T={T} decode rows, d={d}, tuned configs) ==")
    print(f"  {'V':>8} {'baseline us':>12} {'fused us':>10} {'speedup':>8} "
          "tuned config")
    key = jax.random.PRNGKey(0)
    sel = {}
    for V in SELECT_VOCABS:
        ks = jax.random.split(key, 3)
        h = jax.random.normal(ks[0], (T, d), jnp.float32) * 0.5
        w = jax.random.normal(ks[1], (d, V), jnp.float32) * 0.1
        m = jax.random.bernoulli(ks[2], 0.7, (T,))
        # the dense decode-step selection ((T, V) logits + full fp32
        # softmax + argmax + gather) IS the kernel package's oracle
        base = jax.jit(select_ref, static_argnames=("softcap",))
        cfg = tuning.resolve("select", V=V)
        fused = jax.jit(lambda h, w, m: fused_select(h, w, m))
        tb = _time(base, h, w, m, iters=iters)
        tf = _time(fused, h, w, m, iters=iters)
        speedup = tb / tf if tf > 0 else float("inf")
        cfg_d = {k: v for k, v in cfg.to_dict().items() if v is not None}
        print(f"  {V:>8} {tb:>12.0f} {tf:>10.0f} {speedup:>7.2f}x {cfg_d}")
        if csv_rows is not None:
            csv_rows.append((f"kernels/select_baseline_V{V}", tb, ""))
            csv_rows.append((f"kernels/select_fused_V{V}", tf,
                             f"{speedup:.2f}"))
        shape = {"T": T, "d": d, "V": V}
        if records is not None:
            records.append(common.record("select", shape, "us_per_call", tf,
                                         config=cfg_d))
            records.append(common.record("select", shape, "us_per_call", tb,
                                         config={"impl": "dense_ref"}))
            records.append(common.record("select", shape, "speedup_vs_dense",
                                         speedup, config=cfg_d))
        sel[f"V{V}"] = {"T": T, "d": d, "baseline_us": tb, "fused_us": tf,
                        "speedup": speedup, "config": cfg_d}
    if results is not None:
        results["select"] = sel
    return sel


def run(csv_rows=None, smoke=False, results=None):
    records = results.setdefault("records", []) if results is not None \
        else None
    run_attention(csv_rows, smoke=smoke, records=records)
    run_select(csv_rows=csv_rows, results=results, smoke=smoke,
               records=records)
    return csv_rows


def main(argv=None):
    ap = common.make_parser(
        description=__doc__,
        smoke_help="CI-sized shapes (fewer rows/iters; same V sweep)")
    ap.add_argument("--tune", action="store_true",
                    help="re-run the kernel config sweep and persist the "
                         "winners to the checked-in tuned table before "
                         "benchmarking")
    ap.add_argument("--tune-ops", default=None, metavar="OP[,OP...]",
                    help="restrict --tune to these ops "
                         f"(default: all of {sorted(tuning.OP_DEFAULTS)})")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.tune:
        ops = tuple(args.tune_ops.split(",")) if args.tune_ops else None
        tuning.run_sweep(ops, vocabs=SELECT_VOCABS,
                         iters=3 if args.smoke else 5)
        tuning.clear_cache()
    results = {"smoke": args.smoke, "select_vocabs": list(SELECT_VOCABS),
               "records": []}
    run(smoke=args.smoke, results=results)
    common.write_results(args.json, results)


if __name__ == "__main__":
    main()
