"""A one-second drive of the whole run on the CPU, at a tiny size with the
kernels interpreted: load generation through EngineDriver, the window's
metrics, the per-layer readers, and the check that decides ``correct`` —
which must pass the program, and fail the float8 control and the timed
path broken underneath."""
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import families, harness, spec  # noqa: E402

# the tiny cell's limit, set as the cells' are (PERF.md): sound runs read
# 0 on seeds 1-10 at this size, the float8 control 0.08-0.16
LIMIT = 0.05


def tiny_cell(tau=0.9, loop="closed"):
    from repro.configs.registry import get_config
    cfg = get_config("qwen2-0.5b").reduced(dtype="bfloat16")
    over = {k: getattr(cfg, k) for k in (
        "n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
        "vocab_size", "mask_token_id", "eos_token_id", "dtype")}
    keys = spec.MODEL_KEYS + families.load("dense").KEYS
    config = dict({k: getattr(cfg, k) for k in keys},
                  arch="qwen2-0.5b", overrides=over, prompt_id_max=500,
                  serve={"max_batch": 4, "block_size": 8, "prompt_len": 32,
                         "gen_length": 32},
                  check_requests=3, trace_seconds=1)
    mix = {"loop": loop, "clients": 8, "rate_per_s": 40.0,
           "conf_threshold": tau, "check_every": 2,
           "max_tokens": {"median": 16, "sigma": 0.8, "min": 8, "max": 32},
           "pool": 64, "lead_in_s": 0.5}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"] = [{"name": "tiny", "config": "qwen2-0.5b",
                           "traffic": "tiny", "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny"]
    return harness.Cell("tiny", config, mix, bench,
                        {"logit_gap": LIMIT, "min_tokens": 16,
                        "slack": 0.05})


def drive(seed, cell=None, **kw):
    return harness.run(cell or tiny_cell(), seed, 1.0, False,
                       time.perf_counter(), require_tpu=False, **kw)


def test_cpu_drive_reports_every_metric():
    out = drive(2**31 + 7, control=True)
    res = out["result"]
    assert res["correct"], res["checks"]
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device", "checks"}
    assert list(res)[-1] == "checks"
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"tok_s", "gap_p95_ms", "ttfb_p95_ms",
                                   "gap_p95_ms.poisson", "setup_s"}
    assert (res["metrics"]["gap_p95_ms.poisson"]["value"]
            == res["metrics"]["gap_p95_ms"]["value"])
    assert res["metrics"]["tok_s"]["value"] > 0
    assert out["diag"]["compiles_in_window"] == 0
    # the float8 control, judged as the program is: not correct
    assert out["diag"]["control_gap"] > LIMIT
    assert out["diag"]["control_correct"] is False
    layer = harness.per_layer(out["view"].cell, out["view"])
    assert {"sched.lanes_per_step", "kv.pool_peak_occupancy",
            "step.iters_per_block"} <= set(layer)
    # random weights reach no tau of 0.9, so those requests take one
    # refinement iteration per token (8); the tau=0 half take one
    assert 1.0 < layer["step.iters_per_block"]["value"] < 8.0
    # no chip: nothing that needs the trace or the peaks is reported
    assert not {"kern.select_roofline", "step.mfu",
                "dev.idle_share.sat"} & set(layer)


def test_cpu_drive_open_loop():
    """Poisson arrivals on schedule, tau=0: every request due in the
    window is counted, and its time to first block is read."""
    out = drive(5, cell=tiny_cell(tau=0.0, loop="open"))
    res = out["result"]
    assert res["correct"], res["checks"]
    # 40 req/s for 1 s: the window's 40 requests, all finished
    assert res["attempted"] == 40 and res["failed"] == 0
    assert res["metrics"]["ttfb_p95_ms"]["value"] > 0
    layer = harness.per_layer(out["view"].cell, out["view"])
    # one refinement forward a block, a second where a candidate was the
    # [MASK] id itself (the tiny vocabulary makes that likely)
    assert 1.0 <= layer["step.iters_per_block"]["value"] < 1.1
    assert layer["sched.queue_wait_p50_ms"]["value"] >= 0
    # the tail read per layer where it is too unsteady to bound end to end
    assert (layer["sched.ttfb_p95_ms"]["value"]
            == res["metrics"]["ttfb_p95_ms"]["value"])


def _alter_token(engine):
    """A served token altered where it is produced: the first token of
    every block a decode step finalizes."""
    inner = engine._jit_decode_block
    P, B = engine.spec.prompt_len, engine.spec.block_size
    mask = engine.cfg.mask_token_id

    def broken(params, state, run, *, sampled):
        new = inner(params, state, run, sampled=sampled)
        lanes = jnp.arange(engine.n_lanes)
        pos = P + jnp.minimum(state.blk, engine.spec.n_blocks - 1) * B
        old = new.tokens[lanes, pos]
        tok = jnp.where(run, (old + 1) % mask, old)
        return new._replace(tokens=new.tokens.at[lanes, pos].set(tok))

    engine._jit_decode_block = broken


def _skip_commit(engine):
    """A step that leaves the cache as it was: finalized blocks are never
    committed, so later blocks decode against a stale cache."""
    inner = engine._jit_decode_block

    def broken(params, state, run, *, sampled):
        return inner(params, state, run, sampled=sampled)._replace(
            cache=state.cache)

    engine._jit_decode_block = broken


@pytest.mark.parametrize("fault", [_alter_token, _skip_commit])
def test_broken_timed_path_is_not_correct(fault):
    res = drive(11, faults=fault)["result"]
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > LIMIT


def test_replay_reads_zero_for_tokens_the_reference_chose():
    """The check itself: tokens the reference picks greedily read 0; one
    token changed reads its own gap."""
    from bench import reference
    from bench.weights import make_weights
    cell = tiny_cell()
    m = cell.model
    w = make_weights(m, 3)
    mkey = tuple(sorted(m.items()))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 500, (1, 32)).astype(np.int32)
    shape = (m["n_layers"], 1, 40, m["n_kv_heads"], m["head_dim"])
    kv = reference._prefill(mkey, False, w, jnp.asarray(prompt),
                            jnp.zeros(shape), jnp.zeros(shape))
    block = np.full((1, 8), m["mask_token_id"], np.int32)
    lg, _, _ = reference._block(mkey, False, w, jnp.asarray(block), *kv, 32)
    top = np.asarray(jnp.argmax(lg, -1)).astype(np.int32)
    res = reference.replay(m, w, prompt, top, [1], [0.0], block_size=8,
                           slack=0.05)
    assert res == {"gap": 0.0, "tokens": 8, "blocks": [0.0],
                   "tau_reads_by_step": [0] * 8}
    bad = top.copy()
    bad[0, 3] = (bad[0, 3] + 1) % 500
    assert reference.replay(m, w, prompt, bad, [1], [0.0], block_size=8,
                            slack=0.05)["gap"] > 0.0
