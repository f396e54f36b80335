"""Bring-up smoke test on one TPU: the CDLM serving path at the published
widths of qwen2-0.5b (24 layers, d=896, 14/2 heads, V=151,936, bf16), with
random weights made from a seed.

    python chip_smoke.py [--seed N]

Phases, each printing its own lines:

1. device — JAX must find a TPU; anything else exits non-zero (there is no
   CPU fallback, and no kernel is allowed to run interpreted);
2. kernels against their oracles on the chip, at the shapes of one decode
   step (8 lanes x one 32-token block): the fused unembed+select kernel at
   T=256 and the paged flash-decode kernel;
3. serve — the continuous engine (paged KV pool, paged decode kernel,
   fused select) behind the stdlib HTTP frontend answers two plain and two
   streamed completions; ``/healthz`` and ``/metrics`` are checked, and
   the traced decode step is shown to run both kernels compiled;
4. reference — the same prompts through the engine's default path (dense
   KV, jnp attention, dense select). The share of agreeing tokens is
   printed, not asserted: bf16 reduction order differs between the paths.

Everything runs in this one process (the HTTP server and its clients are
threads), so the chip is held once. The compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache/`` of the
checkout. The last line of stdout is one JSON object naming the device;
any failed check exits non-zero before it is printed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ServeConfig
from repro.configs.registry import get_config
from repro.kernels import pallas_calls
from repro.kernels.decode_attn import paged_decode_attention
from repro.kernels.decode_attn.ref import paged_decode_attention_ref
from repro.kernels.select import fused_select, select_ref
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_model, unembed_matrix
from repro.serving import GenerationRequest, make_engine
from repro.serving.server import serve_http

ARCH = "qwen2-0.5b"
MAX_BATCH, BLOCK, PROMPT_LEN, GEN_LEN, TAU = 8, 32, 512, 256, 0.9
# bf16 inputs, fp32 accumulation: products are exact, only the summation
# order differs between kernel and oracle
CONF_RTOL = 1e-2
LOGIT_ATOL = 1e-2
ATTN_ATOL = 2e-2


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def device_check():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but JAX found platform "
            f"{dev.platform!r} ({dev.device_kind}); there is no fallback")
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}")
    return dev


def kernel_checks(cfg, params, seed: int) -> None:
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 8)
    V, d = cfg.vocab_size, cfg.d_model

    # fused select: one refinement step's rows (8 lanes x 32 tokens)
    T = MAX_BATCH * BLOCK
    w = unembed_matrix(params, cfg)
    h = jax.random.normal(ks[0], (T, d), jnp.float32).astype(cfg.dtype)
    masked = jax.random.bernoulli(ks[1], 0.7, (T,))
    t0 = time.perf_counter()
    cand, conf = fused_select(h, w, masked, impl="pallas", interpret=False)
    cand.block_until_ready()
    dt = time.perf_counter() - t0
    logits = jax.jit(lambda h, w: h.astype(jnp.float32)
                     @ w.astype(jnp.float32))(h, w)
    _, ref_conf = jax.jit(select_ref)(h, w, masked)
    row_max = jnp.max(logits, axis=-1)
    cand_logit = jnp.take_along_axis(logits, cand[:, None], axis=-1)[:, 0]
    logit_gap = float(jnp.max(row_max - cand_logit))
    m = np.asarray(masked)
    conf, ref_conf = np.asarray(conf), np.asarray(ref_conf)
    conf_err = float(np.max(np.abs(conf[m] - ref_conf[m])
                            / np.abs(ref_conf[m])))
    check(bool(((np.asarray(cand) >= 0) & (np.asarray(cand) < V)).all()),
          "fused_select candidate out of vocab")
    check(logit_gap <= LOGIT_ATOL,
          f"fused_select candidate logit {logit_gap:.3g} below row max")
    check(conf_err <= CONF_RTOL,
          f"fused_select confidence rel err {conf_err:.3g}")
    check(bool(np.all(np.isneginf(conf[~m]))),
          "fused_select finalized rows must have -inf confidence")
    print(f"kernel fused_select T={T} d={d} V={V} {cfg.dtype}: ok "
          f"(conf max rel err {conf_err:.3g}, candidate logit gap "
          f"{logit_gap:.3g}, first call incl. compile {dt:.2f}s)")

    # paged flash-decode: 8 lanes at mixed offsets over a scattered pool
    Kv, G, hd = cfg.n_kv_heads, cfg.q_per_kv, cfg.head_dim
    n_t = -(-(PROMPT_LEN + GEN_LEN) // BLOCK)
    n_pages = MAX_BATCH * n_t
    dt_ = cfg.dtype
    q = jax.random.normal(ks[2], (MAX_BATCH, BLOCK, Kv, G, hd)).astype(dt_)
    kp = jax.random.normal(ks[3], (n_pages, BLOCK, Kv, hd)).astype(dt_)
    vp = jax.random.normal(ks[4], (n_pages, BLOCK, Kv, hd)).astype(dt_)
    kb = jax.random.normal(ks[5], (MAX_BATCH, BLOCK, Kv, hd)).astype(dt_)
    vb = jax.random.normal(ks[6], (MAX_BATCH, BLOCK, Kv, hd)).astype(dt_)
    rng = np.random.default_rng(seed)
    lens = PROMPT_LEN + BLOCK * rng.integers(0, GEN_LEN // BLOCK, MAX_BATCH)
    perm = rng.permutation(n_pages)
    table = np.full((MAX_BATCH, n_t), -1, np.int32)
    for lane, ln in enumerate(lens):
        used = -(-int(ln) // BLOCK)
        table[lane, :used] = perm[lane * n_t:lane * n_t + used]
    table, lens = jnp.asarray(table), jnp.asarray(lens, jnp.int32)
    scale = hd ** -0.5
    t0 = time.perf_counter()
    out = paged_decode_attention(q, kp, vp, kb, vb, table, lens,
                                 scale=scale, interpret=False)
    out.block_until_ready()
    dt = time.perf_counter() - t0
    f32 = [x.astype(jnp.float32) for x in (q, kp, vp, kb, vb)]
    ref = jax.jit(lambda *a: paged_decode_attention_ref(
        *a, scale=scale))(*f32, table, lens)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
    check(err <= ATTN_ATOL, f"paged decode attention max abs err {err:.3g}")
    print(f"kernel paged_decode_attention b={MAX_BATCH} Bq={BLOCK} Kv={Kv} "
          f"G={G} hd={hd} pages={n_pages}x{BLOCK} {cfg.dtype}: ok (max abs "
          f"err {err:.3g}, first call incl. compile {dt:.2f}s)")


def _post(base: str, body: dict):
    req = urllib.request.Request(
        f"{base}/v1/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=600)


def complete(base: str, prompt, stream: bool):
    """One completion; returns (token ids, finish_reason, wall seconds)."""
    t0 = time.perf_counter()
    body = {"prompt": [int(t) for t in prompt], "stream": stream}
    with _post(base, body) as r:
        if not stream:
            choice = json.load(r)["choices"][0]
            return (choice["token_ids"], choice["finish_reason"],
                    time.perf_counter() - t0)
        ids, reason, done = [], None, False
        for raw in r:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            data = line[len("data: "):]
            if data == "[DONE]":
                done = True
                break
            choice = json.loads(data)["choices"][0]
            ids.extend(choice["token_ids"])
            reason = choice["finish_reason"] or reason
    check(done, "SSE stream ended without [DONE]")
    return ids, reason, time.perf_counter() - t0


def serve_checks(cfg, params, prompts):
    serve = ServeConfig(max_batch=MAX_BATCH, block_size=BLOCK,
                        gen_length=GEN_LEN, conf_threshold=TAU,
                        sampler="cdlm", scheduler="continuous",
                        cache_layout="paged", fused_select=True)
    eng = make_engine(params, cfg, serve, prompt_len=PROMPT_LEN,
                      use_paged_kernel=True)
    t0 = time.perf_counter()
    eng.warmup(per_request=True)
    print(f"serve: engine compile/warm-up {time.perf_counter() - t0:.2f}s "
          f"(continuous, paged, paged kernel, fused select, "
          f"max_batch={MAX_BATCH}, prompt={PROMPT_LEN}, gen={GEN_LEN})")

    server = serve_http(eng, "127.0.0.1", 0, block=False)
    base = "http://127.0.0.1:%d" % server.server_address[1]
    try:
        with urllib.request.urlopen(f"{base}/healthz", timeout=30) as r:
            check(json.load(r)["status"] == "ok", "/healthz not ok")
        # two plain, two streamed; the third repeats the first's prompt
        jobs = [(prompts[0], False), (prompts[1], False),
                (prompts[0], True), (prompts[2], True)]
        t0 = time.perf_counter()
        with ThreadPoolExecutor(len(jobs)) as pool:
            futs = [pool.submit(complete, base, p, s) for p, s in jobs]
            results = [f.result() for f in futs]
        wall = time.perf_counter() - t0
        with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
            metrics = r.read().decode()
        with urllib.request.urlopen(f"{base}/healthz", timeout=30) as r:
            check(json.load(r)["status"] == "ok", "/healthz not ok after")
    finally:
        server.shutdown()
        server.server_close()

    for (_, stream), (ids, reason, dt) in zip(jobs, results):
        kind = "streamed" if stream else "plain"
        check(reason is not None, f"{kind} completion without finish_reason")
        check(1 <= len(ids) <= GEN_LEN,
              f"{kind} completion has {len(ids)} tokens")
        check(all(0 <= t < cfg.vocab_size for t in ids),
              f"{kind} completion has ids outside [0, {cfg.vocab_size})")
        print(f"serve: {kind} completion {len(ids)} tokens, "
              f"finish_reason={reason}, wall {dt:.3f}s")
    check(results[0][0] == results[2][0],
          "streamed and plain completions of one prompt differ")
    print("serve: streamed == plain token ids for the repeated prompt")
    check(f"cdlm_requests_completed_total {len(jobs)}" in metrics,
          f"/metrics does not count {len(jobs)} completed requests")
    print(f"serve: {len(jobs)} completions in {wall:.3f}s wall; "
          f"mean wall per request "
          f"{np.mean([r[2] for r in results]):.3f}s; /healthz ok; "
          f"/metrics counts {len(jobs)} completed")

    run = np.ones((MAX_BATCH,), bool)
    step = jax.make_jaxpr(
        lambda p, s, r: eng._decode_block(p, s, r, sampled=False))(
            params, eng._state, run)
    calls = pallas_calls(step)
    names = sorted({n for n, _ in calls})
    check({"_select_kernel", "_paged_decode_kernel"} <= set(names),
          f"decode step runs kernels {names}, not fused select + paged "
          "decode")
    check(not any(interp for _, interp in calls),
          "a kernel in the decode step is interpreted")
    print(f"serve: decode step runs {len(calls)} Pallas calls {names}, "
          "none interpreted")
    return [results[0][0], results[1][0], results[3][0]]


def reference_agreement(cfg, params, prompts, kernel_ids) -> None:
    serve = ServeConfig(max_batch=MAX_BATCH, block_size=BLOCK,
                        gen_length=GEN_LEN, conf_threshold=TAU,
                        sampler="cdlm", scheduler="continuous")
    eng = make_engine(params, cfg, serve, prompt_len=PROMPT_LEN)
    t0 = time.perf_counter()
    eng.warmup()
    warm = time.perf_counter() - t0
    outs = {o.id: o for o in eng.generate(
        [GenerationRequest(prompt=p, id=i) for i, p in enumerate(prompts)])}
    same = total = 0
    for i, ids in enumerate(kernel_ids):
        ref = np.asarray(outs[i].tokens)[:len(ids)]
        same += int(np.sum(ref == np.asarray(ids)))
        total += len(ids)
    print(f"reference: dense/jnp/dense-select path (warm-up {warm:.2f}s) "
          f"agrees on {same}/{total} tokens ({same / total:.4f}); "
          "informational, not asserted")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, prompts and inputs")
    args = ap.parse_args()

    dev = device_check()
    cache_dir = enable_compile_cache()
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"compile cache: {cache_dir} ({entries} entries at start)")

    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    params = init_model(jax.random.PRNGKey(args.seed), cfg)
    jax.block_until_ready(params)
    print(f"model: {ARCH} layers={cfg.n_layers} d={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} V={cfg.vocab_size} "
          f"{cfg.dtype}, {cfg.param_count() / 1e6:.1f}M params, random "
          f"init seed={args.seed} in {time.perf_counter() - t0:.2f}s")

    kernel_checks(cfg, params, args.seed)
    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.mask_token_id, (3, PROMPT_LEN),
                           dtype=np.int32)
    kernel_ids = serve_checks(cfg, params, prompts)
    reference_agreement(cfg, params, prompts, kernel_ids)

    stats = dev.memory_stats() or {}
    print(f"memory: peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
