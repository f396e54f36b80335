"""Continuous block-level batching engine: scheduling behavior and THE
serving invariant — mid-flight lane recycling is loss-free (a request
admitted into a freed lane decodes exactly as it would in isolation)."""
import jax
import numpy as np
import pytest

from repro.configs.base import ServeConfig
from repro.configs.registry import get_config
from repro.serving import ContinuousEngine, Engine, Request, make_engine

CFG = get_config("qwen2-0.5b").reduced(dtype="float32")
P, G, B = 8, 16, 4


def _serve(scheduler="continuous", max_batch=2, sampler="cdlm"):
    return ServeConfig(max_batch=max_batch, block_size=B, gen_length=G,
                       sampler=sampler, conf_threshold=0.5,
                       scheduler=scheduler)


@pytest.fixture(scope="module")
def params():
    from repro.models import init_model
    return init_model(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def requests():
    rng = np.random.default_rng(0)
    return [Request(prompt=rng.integers(2, CFG.vocab_size, P,
                                        dtype=np.int32), id=i)
            for i in range(5)]


def test_empty_request_list(params):
    eng = Engine(params, CFG, _serve("static"), prompt_len=P)
    assert eng.generate([]) == []
    ceng = ContinuousEngine(params, CFG, _serve(), prompt_len=P)
    assert ceng.generate([]) == []


def test_mismatched_extras_raise(params):
    eng = Engine(params, CFG, _serve("static"), prompt_len=P)
    reqs = [Request(prompt=np.zeros(P, np.int32), id=0,
                    extras={"encoder_embeds": np.zeros((3, 4))}),
            Request(prompt=np.zeros(P, np.int32), id=1)]
    with pytest.raises(ValueError, match="extras"):
        eng.generate(reqs)


def test_continuous_requires_cdlm(params):
    with pytest.raises(ValueError, match="cdlm"):
        ContinuousEngine(params, CFG, _serve(sampler="fast_dllm"),
                         prompt_len=P)


def test_continuous_sampled_decoding_is_isolation_exact(params, requests):
    """Sampled decoding runs on per-lane RNG streams (advanced only on a
    lane's own active iterations), so a sampled request decodes
    bit-identically to its isolated decode regardless of batch company."""
    serve = ServeConfig(max_batch=2, block_size=B, gen_length=G,
                        sampler="cdlm", conf_threshold=0.5,
                        scheduler="continuous", temperature=0.7)
    eng = ContinuousEngine(params, CFG, serve, prompt_len=P)
    eng.warmup()
    batched = {r.id: r for r in eng.generate(list(requests))}
    for req in requests[:3]:
        solo = eng.generate([Request(prompt=req.prompt, id=req.id)])[0]
        got = batched[req.id]
        assert np.array_equal(solo.tokens, got.tokens), req.id
        assert solo.steps == got.steps, req.id


def test_make_engine_dispatch(params):
    assert isinstance(make_engine(params, CFG, _serve("static"),
                                  prompt_len=P), Engine)
    assert isinstance(make_engine(params, CFG, _serve("continuous"),
                                  prompt_len=P), ContinuousEngine)
    with pytest.raises(ValueError, match="scheduler"):
        make_engine(params, CFG, _serve("bogus"), prompt_len=P)


def test_continuous_serves_more_requests_than_lanes(params, requests):
    """5 requests through 2 lanes: every request completes exactly once,
    with queueing visible in the accounting."""
    eng = ContinuousEngine(params, CFG, _serve(max_batch=2), prompt_len=P)
    eng.warmup()
    resp = eng.generate(requests)
    assert sorted(r.id for r in resp) == [0, 1, 2, 3, 4]
    for r in resp:
        assert r.tokens.shape == (G,)
        assert 0 < r.gen_length <= G
        assert r.latency_s >= r.queue_s >= 0.0
    # at least one request had to wait for a lane
    assert max(r.queue_s for r in resp) > 0.0


def test_mid_flight_eviction_is_exact(params, requests):
    """THE invariant: a request admitted into a recycled lane (mid-flight,
    after a short request freed it) produces exactly the tokens and steps it
    produces when decoded alone — cache-row reset leaves no residue."""
    eng = ContinuousEngine(params, CFG, _serve(max_batch=2), prompt_len=P)
    eng.warmup()
    # short requests (1 block) finish first and free lanes for the rest
    mixed = [Request(prompt=r.prompt, id=r.id,
                     max_tokens=B if r.id < 2 else None) for r in requests]
    stream = {r.id: r for r in eng.generate(mixed)}
    for req in mixed:
        solo = eng.generate([Request(prompt=req.prompt, id=req.id,
                                     max_tokens=req.max_tokens)])[0]
        got = stream[req.id]
        assert np.array_equal(solo.tokens, got.tokens), req.id
        assert solo.steps == got.steps, req.id
        assert solo.gen_length == got.gen_length, req.id


def test_max_tokens_caps_generation(params, requests):
    eng = ContinuousEngine(params, CFG, _serve(max_batch=2), prompt_len=P)
    eng.warmup()
    resp = eng.generate([Request(prompt=requests[0].prompt, id=0,
                                 max_tokens=B)])
    assert resp[0].gen_length <= B
    # the returned span is sliced to the cap (same contract as the
    # static engine — no [MASK] filler past max_tokens)
    assert resp[0].tokens.shape == (B,)


def test_arrival_trace_ordering(params, requests):
    """Requests arriving later are admitted later (queue_s reflects the
    trace), and everything still completes."""
    eng = ContinuousEngine(params, CFG, _serve(max_batch=2), prompt_len=P)
    eng.warmup()
    staggered = [Request(prompt=r.prompt, id=r.id,
                         arrival_s=0.05 * r.id) for r in requests]
    resp = eng.generate(staggered)
    assert sorted(r.id for r in resp) == [0, 1, 2, 3, 4]
    assert all(r.latency_s >= 0 for r in resp)


def test_bf16_paged_kernel_fused_select_engine(monkeypatch):
    """CPU rehearsal of the chip's serving path: the continuous engine in
    bfloat16 (the dtype of every published config) over the paged layout,
    with the paged decode kernel and the Pallas fused select, both in
    interpret mode. Every request completes with in-vocab ids, and the
    decode step really contains both kernels."""
    import functools

    from repro.core import diffusion
    from repro.kernels import pallas_calls
    from repro.models import init_model

    # off the TPU, fused select resolves "auto" to the streaming scan;
    # steer it to the Pallas kernel (interpreted here) for this test only
    monkeypatch.setattr(diffusion, "confidence_and_candidates_fused",
                        functools.partial(
                            diffusion.confidence_and_candidates_fused,
                            impl="pallas"))
    cfg = get_config("qwen2-0.5b").reduced(dtype="bfloat16")
    params = init_model(jax.random.PRNGKey(1), cfg)
    serve = ServeConfig(max_batch=2, block_size=B, gen_length=G,
                        sampler="cdlm", conf_threshold=0.5,
                        scheduler="continuous", cache_layout="paged",
                        fused_select=True)
    eng = make_engine(params, cfg, serve, prompt_len=P,
                      use_paged_kernel=True)
    eng.warmup(per_request=True)
    rng = np.random.default_rng(1)
    reqs = [Request(prompt=rng.integers(2, cfg.mask_token_id, P,
                                        dtype=np.int32), id=i)
            for i in range(3)]
    resp = eng.generate(reqs)
    assert sorted(r.id for r in resp) == [0, 1, 2]
    for r in resp:
        assert r.finish_reason in ("stop", "length")
        assert 1 <= r.gen_length <= G
        ids = np.asarray(r.tokens)[:r.gen_length]
        assert ((ids >= 0) & (ids < cfg.vocab_size)).all()

    run = np.ones((serve.max_batch,), bool)
    step = jax.make_jaxpr(
        lambda p, s, r: eng._decode_block(p, s, r, sampled=False))(
            params, eng._state, run)
    names = {name for name, _ in pallas_calls(step)}
    assert {"_select_kernel", "_paged_decode_kernel"} <= names, names
