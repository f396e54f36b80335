"""HTTP serving smoke: boot the stdlib frontend on a tiny random-init
CDLM engine, run one streamed and one non-streamed completion through
``urllib``, and assert both are token-identical to ``Engine.generate``
on an identical reference engine.

    PYTHONPATH=src python -m benchmarks.serve_smoke

Exercises, end to end: ``add_request``/``step()`` under the driver
thread, SSE block streaming (chunks must reassemble to the exact batch
decode), ``/healthz`` and ``/metrics``. Used by the CI ``serve-smoke``
job (``make serve-smoke``).
"""
from __future__ import annotations

import json
import os
import sys
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.configs.base import ServeConfig
from repro.configs.registry import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_model
from repro.serving import Request, make_engine
from repro.serving.server import serve_http

P, G, B = 8, 16, 4
CFG = get_config("qwen2-0.5b").reduced(dtype="float32")
SERVE = ServeConfig(max_batch=2, block_size=B, gen_length=G, sampler="cdlm",
                    conf_threshold=0.5, scheduler="continuous")


def _post(base, body):
    req = urllib.request.Request(
        f"{base}/v1/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=120)


def main():
    enable_compile_cache()
    params = init_model(jax.random.PRNGKey(0), CFG)
    rng = np.random.default_rng(0)
    prompt = rng.integers(2, CFG.vocab_size, P, dtype=np.int32)

    eng = make_engine(params, CFG, SERVE, prompt_len=P)
    eng.warmup(per_request=True)
    server = serve_http(eng, "127.0.0.1", 0, block=False)
    base = "http://127.0.0.1:%d" % server.server_address[1]

    # reference: identical engine, batch generate
    ref_eng = make_engine(params, CFG, SERVE, prompt_len=P)
    ref_eng.warmup()
    ref = ref_eng.generate([Request(prompt=prompt, id=0)])[0]
    ref_ids = np.asarray(ref.tokens)[:ref.gen_length].tolist()

    with urllib.request.urlopen(f"{base}/healthz", timeout=30) as r:
        assert json.load(r)["status"] == "ok"

    with _post(base, {"prompt": prompt.tolist()}) as r:
        full = json.load(r)
    got_full = full["choices"][0]["token_ids"]
    assert got_full == ref_ids, (got_full, ref_ids)
    print(f"non-streamed: {len(got_full)} tokens, "
          f"finish={full['choices'][0]['finish_reason']} — matches "
          "Engine.generate")

    got_stream, chunks = [], 0
    with _post(base, {"prompt": prompt.tolist(), "stream": True}) as r:
        for raw in r:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            data = line[len("data: "):]
            if data == "[DONE]":
                break
            got_stream.extend(json.loads(data)["choices"][0]["token_ids"])
            chunks += 1
    assert got_stream == ref_ids, (got_stream, ref_ids)
    print(f"streamed: {chunks} block chunks reassemble to the same "
          f"{len(got_stream)} tokens")

    with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
        metrics = r.read().decode()
    assert "cdlm_requests_completed_total 2" in metrics, metrics
    assert "cdlm_lanes_peak_lanes" in metrics
    print("metrics: requests_completed_total=2, lane/page gauges exported")

    server.shutdown()
    print("serve smoke OK")


if __name__ == "__main__":
    main()
