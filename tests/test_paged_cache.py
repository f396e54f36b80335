"""Dense/paged cache-layout equivalence suite.

THE layout invariant: the block-paged cache is a pure memory-layout change —
logits, tokens, steps, and cache commits are *bit-identical* to the dense
layout, from a single cached block decode up through the continuous serving
engine (including page-starved scheduling: stalls and preemptions)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _jaxpr_walk import paged_kernel_calls

from repro.configs.base import ServeConfig
from repro.configs.registry import get_config
from repro.core import cache as C
from repro.core import masks
from repro.core.block_loop import STRATEGIES, SamplerSpec, run_block_loop
from repro.models import forward, init_model
from repro.serving import ContinuousEngine, Request

CFG = get_config("qwen2-0.5b").reduced(dtype="float32")
P, G, B = 8, 16, 4
T = P + G


@pytest.fixture(scope="module")
def params():
    return init_model(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def requests():
    rng = np.random.default_rng(0)
    return [Request(prompt=rng.integers(2, CFG.vocab_size, P,
                                        dtype=np.int32), id=i)
            for i in range(5)]


def _serve(max_batch=2, **kw):
    return ServeConfig(max_batch=max_batch, block_size=B, gen_length=G,
                       sampler="cdlm", conf_threshold=0.5,
                       scheduler="continuous", **kw)


@pytest.fixture(scope="module")
def dense_responses(params, requests):
    eng = ContinuousEngine(params, CFG, _serve(), prompt_len=P)
    eng.warmup()
    return {r.id: r for r in eng.generate(requests)}


def _assert_same_responses(ref, got):
    assert sorted(got) == sorted(ref)
    for i in ref:
        assert np.array_equal(ref[i].tokens, got[i].tokens), i
        assert ref[i].steps == got[i].steps, i
        assert ref[i].gen_length == got[i].gen_length, i


# ---------------------------------------------------------------------------
# Forward / block-loop equivalence
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["qwen2-0.5b", "gemma2-27b",
                                  "kimi-k2-1t-a32b"])
def test_cached_block_decode_paged_bitwise(arch):
    """A cached block decode through the paged gather path is bit-identical
    to the dense cache — softcap/SWA (gemma2) and MoE (kimi) included."""
    cfg = get_config(arch).reduced(dtype="float32")
    params = init_model(jax.random.PRNGKey(0), cfg)
    b = 2
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, T), 2,
                                cfg.vocab_size)
    out = forward(params, tokens[:, :P], cfg=cfg, mode=masks.BLOCK_CAUSAL,
                  prompt_len=P, block_size=B, moe_dropless=True)
    rows = jnp.ones((b,), bool)
    dense = C.commit_rows(C.init_cache(cfg, b, T, dtype="float32"),
                          out.emissions, 0, rows)
    paged = C.init_paged_cache(cfg, b, T, n_pages=b * (T // B), page_size=B,
                               dtype="float32")
    paged, _ = C.alloc(paged, rows, 0, T)
    paged = C.commit_rows(paged, out.emissions, 0, rows)

    kw = dict(cfg=cfg, mode=masks.BLOCK_CAUSAL, prompt_len=P, block_size=B,
              positions=P + jnp.arange(B), cache_len=P)
    want = forward(params, tokens[:, P:P + B], cache=dense, **kw)
    got = forward(params, tokens[:, P:P + B], cache=paged, **kw)
    assert np.array_equal(np.asarray(want.logits), np.asarray(got.logits))


def test_run_block_loop_paged_equals_dense(params):
    spec = SamplerSpec(prompt_len=P, gen_len=G, block_size=B,
                       conf_threshold=0.5)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, P), 2,
                                 CFG.vocab_size)
    key = jax.random.PRNGKey(2)
    want = run_block_loop(params, prompts, cfg=CFG, spec=spec,
                          strategy=STRATEGIES["cdlm"], key=key)
    spec_p = dataclasses.replace(spec, cache_layout="paged")
    got = jax.jit(
        lambda p, x, k: run_block_loop(p, x, cfg=CFG, spec=spec_p,
                                       strategy=STRATEGIES["cdlm"], key=k)
    )(params, prompts, key)
    assert np.array_equal(np.asarray(want.tokens), np.asarray(got.tokens))
    assert np.array_equal(np.asarray(want.steps), np.asarray(got.steps))
    assert int(want.n_model_calls) == int(got.n_model_calls)


def test_paged_requires_exact_commit(params):
    spec = SamplerSpec(prompt_len=P, gen_len=G, block_size=B,
                       cache_layout="paged")
    prompts = jnp.zeros((1, P), jnp.int32)
    with pytest.raises(ValueError, match="exact-commit"):
        run_block_loop(params, prompts, cfg=CFG, spec=spec,
                       strategy=STRATEGIES["fast_dllm"])


# ---------------------------------------------------------------------------
# Continuous-engine equivalence across pool pressure
# ---------------------------------------------------------------------------
def test_engine_paged_equals_dense(params, requests, dense_responses):
    eng = ContinuousEngine(params, CFG, _serve(cache_layout="paged"),
                           prompt_len=P)
    eng.warmup()
    _assert_same_responses(dense_responses,
                           {r.id: r for r in eng.generate(requests)})
    stats = eng.page_pool_stats()
    assert stats["n_pages"] == 2 * (T // B)
    assert 0 < stats["peak_pages"] <= stats["n_pages"]


def test_engine_minimum_pool_exact_with_page_reuse(params, requests,
                                                   dense_responses):
    """A pool holding exactly ONE full canvas: optimistic admission still
    lets a second lane in (prompt + next block fit), so requests constantly
    contend for pages and recycle them — outputs must still be
    bit-identical."""
    eng = ContinuousEngine(
        params, CFG, _serve(cache_layout="paged", page_pool_pages=T // B),
        prompt_len=P)
    eng.warmup()
    _assert_same_responses(dense_responses,
                           {r.id: r for r in eng.generate(requests)})
    stats = eng.page_pool_stats()
    assert stats["peak_occupancy"] == 1.0
    assert stats["preemptions"] + stats["stall_rounds"] > 0


def test_engine_tight_pool_exact_under_preemption(params, requests,
                                                  dense_responses):
    """A pool too small for two full canvases forces stalls/preemptions;
    preempted requests re-decode from scratch — still loss-free."""
    eng = ContinuousEngine(
        params, CFG,
        _serve(cache_layout="paged", page_pool_pages=T // B + 2),
        prompt_len=P)
    eng.warmup()
    _assert_same_responses(dense_responses,
                           {r.id: r for r in eng.generate(requests)})
    stats = eng.page_pool_stats()
    assert stats["preemptions"] + stats["stall_rounds"] > 0


def test_engine_mixed_max_tokens_paged(params, requests, dense_responses):
    """Mixed generation caps through a tight pool: short requests free pages
    early; every request still matches its solo decode."""
    eng = ContinuousEngine(
        params, CFG,
        _serve(cache_layout="paged", page_pool_pages=T // B + 2),
        prompt_len=P)
    eng.warmup()
    mixed = [Request(prompt=r.prompt, id=r.id,
                     max_tokens=B if r.id < 2 else None) for r in requests]
    got = {r.id: r for r in eng.generate(mixed)}
    for req in mixed:
        solo = eng.generate([Request(prompt=req.prompt, id=req.id,
                                     max_tokens=req.max_tokens)])[0]
        assert np.array_equal(solo.tokens, got[req.id].tokens), req.id
        assert solo.steps == got[req.id].steps, req.id


def test_engine_paged_kernel_path(params, requests, dense_responses):
    """use_paged_kernel=True routes decode through the Pallas page-table
    kernel (interpret mode on CPU). Not bit-equal to the gather path
    (reduction order differs) but the toy fixture's confidences sit far
    from the threshold, so tokens/steps must still match."""
    eng = ContinuousEngine(params, CFG, _serve(cache_layout="paged"),
                           prompt_len=P, use_paged_kernel=True)
    eng.warmup()
    _assert_same_responses(dense_responses,
                           {r.id: r for r in eng.generate(requests)})


def test_engine_paged_kernel_one_call_per_layer_scan(params):
    """The engine's decode step runs the paged kernel once per layer-scan
    body for all lanes (refinement forward and commit forward): the lane
    vmap reaches the kernel as a (lanes, n_tables) page table, with no
    per-lane loop between the layer scan and the kernel."""
    eng = ContinuousEngine(params, CFG, _serve(cache_layout="paged"),
                           prompt_len=P, use_paged_kernel=True)
    run = jnp.ones((eng.n_lanes,), bool)
    calls = paged_kernel_calls(jax.make_jaxpr(
        lambda p, s, r: eng._decode_block(p, s, r, sampled=False))(
            params, eng._state, run))
    n_t = eng._state.cache.page_table.shape[1]
    assert [shape for shape, _ in calls] == [(eng.n_lanes, n_t)] * 2
    for _, outer in calls:
        assert "scan" in outer
        assert "while" not in outer[outer.index("scan"):]


def test_paged_kernel_requires_paged_layout(params):
    with pytest.raises(ValueError, match="use_paged_kernel"):
        ContinuousEngine(params, CFG, _serve(), prompt_len=P,
                         use_paged_kernel=True)


def test_static_engine_rejects_pool_sizing(params):
    from repro.serving import Engine
    serve = ServeConfig(max_batch=2, block_size=B, gen_length=G,
                        sampler="cdlm", scheduler="static",
                        cache_layout="paged", page_pool_pages=6)
    with pytest.raises(ValueError, match="page_pool_pages"):
        Engine(params, CFG, serve, prompt_len=P)


def test_pool_undersized_raises(params):
    with pytest.raises(ValueError, match="deadlock-free minimum"):
        ContinuousEngine(
            params, CFG,
            _serve(cache_layout="paged", page_pool_pages=T // B - 1),
            prompt_len=P)


def test_unknown_layout_raises(params):
    with pytest.raises(ValueError, match="cache layout"):
        ContinuousEngine(params, CFG, _serve(cache_layout="bogus"),
                         prompt_len=P)


def test_dense_layout_rejects_pool_sizing(params):
    """page_pool_pages with the dense layout would be silently ignored —
    reject it so memory-budget comparisons can't be misconfigured."""
    with pytest.raises(ValueError, match="page_pool_pages"):
        ContinuousEngine(params, CFG, _serve(page_pool_pages=12),
                         prompt_len=P)
