"""Model families (``bench/families/``): what a configuration brings as
files of its own, and pins that hold the benchmark's yardsticks where they
stood before the dense layer moved into a family of its own.

The pins were computed with the harness as it was before the split, at a
tiny size with the model keys of both configuration files (weights, the
reference and the float8 control) and at their published widths (FLOP
and byte counts, and every per-layer reader on the recorded chip trace).
"""
import hashlib
import json
import math
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import cost, harness  # noqa: E402
from bench import trace as tr  # noqa: E402
from bench.peaks import peaks  # noqa: E402

CONFIGS = ("qwen2-0.5b", "dream-7b-l14")
# the model keys a dense configuration file carries
DENSE_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab_size", "qkv_bias", "tie_embeddings",
              "rope_theta", "norm_eps", "mask_token_id", "eos_token_id")
TINY = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 16, "d_ff": 96, "vocab_size": 256,
        "mask_token_id": 255, "eos_token_id": 254}
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "qwen_sat_trace.json.gz")


def _config(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def _model(name, tiny=False):
    c = _config(name)
    return {k: TINY[k] if tiny and k in TINY else c[k] for k in DENSE_KEYS}


# ---------------------------------------------------------------------------
# readings, computed the same way before and after the split
# ---------------------------------------------------------------------------
def weight_digests(m, seed):
    """Leaf name -> first 16 hex digits of sha256 over its dtype, shape
    and bytes."""
    from bench.weights import make_weights
    out = {}
    for name, a in sorted(make_weights(m, seed).items()):
        a = np.asarray(a)
        h = hashlib.sha256(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
        out[name] = h.hexdigest()[:16]
    return out


def program_layout(m, seed):
    """Path in the program's params tree -> the flat leaf placed there."""
    import jax
    from bench import weights
    w = weights.make_weights(m, seed)
    tree = weights.to_program_layout(w, m)
    by_id = {id(v): k for k, v in w.items()}
    return {jax.tree_util.keystr(p): by_id[id(v)]
            for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def replay_readings(m):
    """The reference and the float8 control over fixed served tokens: a
    tau=0 request over two blocks and a tau=0.9 one over one."""
    from bench import reference
    from bench.weights import make_weights
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, 250, (2, 16)).astype(np.int32)
    served = rng.integers(0, 250, (2, 16)).astype(np.int32)
    return reference.replay(m, make_weights(m, 5), prompts, served, [2, 1],
                            [0.0, 0.9], block_size=8, slack=0.05,
                            control=True)


def cost_readings(m):
    return {
        "select_512": cost.select_call(m, 512),
        "paged_attn": cost.paged_attn_call(m, 32, [512, 544, 736]),
        "fwd_block": cost.forward_flops(m, 32, 512, True),
        "fwd_commit": cost.forward_flops(m, 32, 736, False),
        "fwd_prefill": cost.forward_flops(m, 512, 0, False),
    }


def reader_view(name):
    """A run's view with the recorded trace and a fixed set of requests,
    steps and pool numbers, so that every reader finds something."""
    from bench.harness import Req, Step
    c = _config(name)
    cell = SimpleNamespace(name=name, model=_model(name), serve=c["serve"])
    reqs = []
    for i in range(24):
        n = 1 + i % 8
        due = 0.45 * i - 1.0
        blocks = [(due + 0.3 + 0.4 * k, k, None) for k in range(n)]
        out = SimpleNamespace(steps=n * (1 + i % 5), queue_s=0.001 * (i % 7))
        reqs.append(Req(i, None, 32 * n, 0.9 if i % 4 else 0.0, due,
                        blocks=blocks, output=out))
    steps = [Step(0.25 * n, 0.25 * n + 0.2,
                  [(lane, (n + lane) % 8, 32 * ((n + lane) % 8))
                   for lane in range(16 - n % 3)]) for n in range(40)]
    return SimpleNamespace(
        cell=cell, reqs=reqs, steps=steps[:36], all_steps=steps, ws=0.0,
        we=9.0, pool={"n_pages": 4096, "peak_occupancy": 0.8125},
        peaks=peaks("TPU v5 lite"), trace=tr.reduce(tr.load(RECORDED)))


def reader_readings(name):
    view = reader_view(name)
    files = sorted(os.listdir(os.path.join(ROOT, "bench", "metrics")))
    return {f[:-3]: harness.load_reader(f[:-3])(view)
            for f in files if f.endswith(".py")}


# ---------------------------------------------------------------------------
# the pins (computed before the split)
# ---------------------------------------------------------------------------
PINS = {'cost': {'dream-7b-l14': {'fwd_block': 247182917632.0,
                                  'fwd_commit': 213741731840.0,
                                  'fwd_prefill': 3393561034752.0,
                                  'paged_attn': {'bytes': 5242880.0,
                                                 'flops': 866123776.0},
                                  'select_512': {'bytes': 1093670912.0,
                                                 'flops': 558077313024.0}},
                 'qwen2-0.5b': {'fwd_block': 33110884352.0,
                                'fwd_commit': 25014829056.0,
                                'fwd_prefill': 388962975744.0,
                                'paged_attn': {'bytes': 1310720.0,
                                               'flops': 216530944.0},
                                'select_512': {'bytes': 273192960.0,
                                               'flops': 139401887744.0}}},
        'layout': {'dream-7b-l14': {"['embed']['head']": 'head',
                                    "['embed']['tok']": 'tok',
                                    "['final_norm']['w']": 'final_norm',
                                    "['slots'][0]['attn']['bk']": 'bk',
                                    "['slots'][0]['attn']['bq']": 'bq',
                                    "['slots'][0]['attn']['bv']": 'bv',
                                    "['slots'][0]['attn']['wk']": 'wk',
                                    "['slots'][0]['attn']['wo']": 'wo',
                                    "['slots'][0]['attn']['wq']": 'wq',
                                    "['slots'][0]['attn']['wv']": 'wv',
                                    "['slots'][0]['mlp']['wi_gate']": 'wi_gate',
                                    "['slots'][0]['mlp']['wi_up']": 'wi_up',
                                    "['slots'][0]['mlp']['wo']": 'wo_mlp',
                                    "['slots'][0]['norm1']['w']": 'norm1',
                                    "['slots'][0]['norm2']['w']": 'norm2'},
                   'qwen2-0.5b': {"['embed']['tok']": 'tok',
                                  "['final_norm']['w']": 'final_norm',
                                  "['slots'][0]['attn']['bk']": 'bk',
                                  "['slots'][0]['attn']['bq']": 'bq',
                                  "['slots'][0]['attn']['bv']": 'bv',
                                  "['slots'][0]['attn']['wk']": 'wk',
                                  "['slots'][0]['attn']['wo']": 'wo',
                                  "['slots'][0]['attn']['wq']": 'wq',
                                  "['slots'][0]['attn']['wv']": 'wv',
                                  "['slots'][0]['mlp']['wi_gate']": 'wi_gate',
                                  "['slots'][0]['mlp']['wi_up']": 'wi_up',
                                  "['slots'][0]['mlp']['wo']": 'wo_mlp',
                                  "['slots'][0]['norm1']['w']": 'norm1',
                                  "['slots'][0]['norm2']['w']": 'norm2'}},
        'readers': {'dream-7b-l14': {'dev.idle_share.poisson': 3.2975203529411856,
                                     'dev.idle_share.sat': 3.2975203529411856,
                                     'kern.paged_attn_roofline': 5.52124272819081,
                                     'kern.select_roofline': 159.29643725282327,
                                     'kv.pool_peak_occupancy': 81.25,
                                     'sched.lanes_per_step': 15.0,
                                     'sched.queue_wait_p50_ms': 3.0,
                                     'sched.ttfb_p95_ms': 300.0000000000007,
                                     'step.admit_share.gap': 12.699057411764704,
                                     'step.admit_share.poisson': 12.699057411764704,
                                     'step.admit_share.sat': 12.699057411764704,
                                     'step.iters_per_block': 2.9220779220779223,
                                     'step.iters_per_block.poisson': 2.9220779220779223,
                                     'step.mfu': 8.191611500522052},
                    'qwen2-0.5b': {'dev.idle_share.poisson': 3.2975203529411856,
                                   'dev.idle_share.sat': 3.2975203529411856,
                                   'kern.paged_attn_roofline': 2.3662468835103465,
                                   'kern.select_roofline': 39.79058733566944,
                                   'kv.pool_peak_occupancy': 81.25,
                                   'sched.lanes_per_step': 15.0,
                                   'sched.queue_wait_p50_ms': 3.0,
                                   'sched.ttfb_p95_ms': 300.0000000000007,
                                   'step.admit_share.gap': 12.699057411764704,
                                   'step.admit_share.poisson': 12.699057411764704,
                                   'step.admit_share.sat': 12.699057411764704,
                                   'step.iters_per_block': 2.9220779220779223,
                                   'step.iters_per_block.poisson': 2.9220779220779223,
                                   'step.mfu': 1.0068975583657078}},
        'replay': {'dream-7b-l14': {'blocks': [6.063915252685547,
                                               2.5926711559295654,
                                               3.8966422080993652],
                                    'control_gap': 0.019835233688354492,
                                    'gap': 6.063915252685547,
                                    'tau_reads_by_step': [1, 0, 0, 0, 0, 0, 0, 0],
                                    'tokens': 17},
                   'qwen2-0.5b': {'blocks': [0.48178982734680176,
                                             0.2817254960536957,
                                             0.8362551927566528],
                                  'control_gap': 0.00884157419204712,
                                  'gap': 0.8362551927566528,
                                  'tau_reads_by_step': [1, 0, 0, 0, 0, 0, 0, 0],
                                  'tokens': 17}},
        'weights': {'dream-7b-l14': {'2147483659': {'bk': '285094d0423263de',
                                                    'bq': '1443a5bacdfa8374',
                                                    'bv': '4c5a421db5a309f1',
                                                    'final_norm': 'cbc60f98e3fb1397',
                                                    'head': '2fbbcabb388ad18a',
                                                    'norm1': '09cb6a380c53a7b2',
                                                    'norm2': 'e33655b66e607cc1',
                                                    'tok': '1c5f977dda99bae8',
                                                    'wi_gate': 'd88a0ce21fc4041d',
                                                    'wi_up': '4675e3bda1be7af0',
                                                    'wk': 'd466db7869f4e7ec',
                                                    'wo': '09fcdc2beedcd7b4',
                                                    'wo_mlp': 'c32002384e6f9aae',
                                                    'wq': 'db0969e904a33280',
                                                    'wv': '6499e5f6335e95e2'},
                                     '3': {'bk': 'fb205c84fcd67345',
                                           'bq': '98d04e9c3b2b9f58',
                                           'bv': '6daef763f5a75719',
                                           'final_norm': '22a3bff020ed34d1',
                                           'head': '867087cd76b2e7f9',
                                           'norm1': '691d18631945ffe2',
                                           'norm2': '71226baf3bafb1fe',
                                           'tok': '9015a46693325de5',
                                           'wi_gate': '3f60a6372c819d3a',
                                           'wi_up': 'f28380408755bfd9',
                                           'wk': 'c2c7d600f0b10fe9',
                                           'wo': '645b065c5b18ae6a',
                                           'wo_mlp': '5094e60fb0e38bf7',
                                           'wq': '49cf3eb1a025c046',
                                           'wv': 'a9e489995d8838b4'}},
                    'qwen2-0.5b': {'2147483659': {'bk': '285094d0423263de',
                                                  'bq': '1443a5bacdfa8374',
                                                  'bv': '4c5a421db5a309f1',
                                                  'final_norm': 'cbc60f98e3fb1397',
                                                  'norm1': 'f8f63490a6d548db',
                                                  'norm2': '09cb6a380c53a7b2',
                                                  'tok': '95be261d59ecaf10',
                                                  'wi_gate': 'f45fbe9f579afe18',
                                                  'wi_up': 'd88a0ce21fc4041d',
                                                  'wk': 'e85d9138c433e263',
                                                  'wo': 'ff56ea7768f65c5e',
                                                  'wo_mlp': 'a498cd4190554dbc',
                                                  'wq': 'f03141c0fdc649f4',
                                                  'wv': '40a787b4396c5622'},
                                   '3': {'bk': 'fb205c84fcd67345',
                                         'bq': '98d04e9c3b2b9f58',
                                         'bv': '6daef763f5a75719',
                                         'final_norm': '22a3bff020ed34d1',
                                         'norm1': 'cc22a97966262f94',
                                         'norm2': '691d18631945ffe2',
                                         'tok': '4ad6e4a25a7173e0',
                                         'wi_gate': '47b74aafce115712',
                                         'wi_up': '3f60a6372c819d3a',
                                         'wk': 'c4f31248bab085bb',
                                         'wo': '031e60915e618035',
                                         'wo_mlp': 'd020ed445f403bcd',
                                         'wq': '618d63df063740ff',
                                         'wv': '1f86479924d1b20e'}}}}


def _same(got, want):
    """Equal to the last bit: floats compared as floats, nothing rounded."""
    assert json.dumps(got, sort_keys=True) == json.dumps(want,
                                                         sort_keys=True)


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_weights_pinned_leaf_by_leaf(name, seed):
    _same(weight_digests(_model(name, tiny=True), seed),
          PINS["weights"][name][str(seed)])


@pytest.mark.parametrize("name", CONFIGS)
def test_program_layout_pinned(name):
    _same(program_layout(_model(name, tiny=True), 3),
          PINS["layout"][name])


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_and_control_pinned(name):
    """Counts exactly; gaps to 1e-5 of their size, since XLA's CPU thread
    pool orders the float32 sums and so moves their last bits with the
    number of threads (1e-7 of a gap, measured), far below what any change
    to the layer's mathematics moves them."""
    got, want = replay_readings(_model(name, tiny=True)), PINS["replay"][name]
    assert got.keys() == want.keys()
    assert got["tokens"] == want["tokens"]
    assert got["tau_reads_by_step"] == want["tau_reads_by_step"]
    for k in ("gap", "control_gap", "blocks"):
        assert got[k] == pytest.approx(want[k], rel=1e-5, abs=0), k


@pytest.mark.parametrize("name", CONFIGS)
def test_costs_pinned_at_published_widths(name):
    _same(cost_readings(_model(name)), PINS["cost"][name])


@pytest.mark.parametrize("name", CONFIGS)
def test_readers_pinned_on_recorded_trace(name):
    """Every reader file on qwen's recorded trace, with either model's
    numbers: dream's kernel shares are no readings of dream's kernels (the
    trace is qwen's), only of the arithmetic that makes them."""
    got = reader_readings(name)
    assert all(v is not None and math.isfinite(v) for v in got.values())
    _same(got, PINS["readers"][name])


# ---------------------------------------------------------------------------
# a family that exists only as a new file
# ---------------------------------------------------------------------------
# appended to a copy of the dense family: one more model key, a kernel kind
# of its own, and every contract function wrapped so that its calls show
PROBE = r"""

import re

KEYS = KEYS + ("activation",)
KERNELS = {"probe_ffn": re.compile(r"custom-call\(bf16\[\d+,\d+,\d+\]")}
CALLS = []


def _seen(f):
    def call(*args):
        CALLS.append(f.__name__)
        return f(*args)
    return call


shapes, program_slot, layer, layer_flops = (
    _seen(f) for f in (shapes, program_slot, layer, layer_flops))
"""


@pytest.fixture
def probe(tmp_path, monkeypatch):
    """The family ``probe``, from a file in a temporary directory that the
    families package searches besides its own."""
    from bench import families
    from bench.families import dense
    with open(dense.__file__) as f:
        (tmp_path / "probe.py").write_text(f.read() + PROBE)
    monkeypatch.setattr(families, "__path__",
                        [*families.__path__, str(tmp_path)])
    monkeypatch.delitem(sys.modules, "bench.families.probe", raising=False)
    yield families.load("probe")
    sys.modules.pop("bench.families.probe", None)


MS = 1_000_000  # ns


def _trace_with_kernels():
    """A compact trace (as ``read_xspace`` gives it) with one op of each
    kernel kind, the probe's included, and one op that is no kernel."""
    text = {"sel.1": "%sel.1 = (s32[32,1]{1,0}, f32[32,1]{1,0}) "
                     "custom-call(bf16[32,64]{1,0} %h)",
            "attn.1": "%attn.1 = bf16[4,8,64]{2,1,0} "
                      "custom-call(s32[4,6]{1,0} %table)",
            "ffn.1": "%ffn.1 = bf16[4,8,96]{2,1,0} "
                     "custom-call(bf16[4,8,64]{2,1,0} %x)",
            "fusion.1": "%fusion.1 = bf16[4,8,64]{2,1,0} fusion(%x)"}
    meta = {k: {"kernel": k != "fusion.1", "text": v, "stats": {}}
            for k, v in text.items()}
    ops = [[k, i * 2 * MS, MS] for i, k in enumerate(text)]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": [["jit__decode_block(1)", 0, 8 * MS]]}],
            "spans": [["bench.window", 0, 8 * MS]], "op_meta": meta}


def test_family_kernels_classify_in_reduce(probe):
    c = _trace_with_kernels()
    assert tr.reduce(c)["kernels"] == {"sel.1": "select",
                                       "attn.1": "paged_attn"}
    assert tr.reduce(c, kernels=probe.KERNELS)["kernels"] == {
        "sel.1": "select", "attn.1": "paged_attn", "ffn.1": "probe_ffn"}
    # the chip trace's own kernels classify as they did
    rec = tr.load(RECORDED)
    own = tr.reduce(rec)["kernels"]
    assert sorted(set(own.values())) == ["paged_attn", "select"]
    assert tr.reduce(rec, kernels=probe.KERNELS)["kernels"] == own
    # a family may not take one of the trace's own kinds
    with pytest.raises(ValueError):
        tr.reduce(c, kernels={"select": probe.KERNELS["probe_ffn"]})


def test_family_file_drives_a_whole_run(probe):
    """The tiny cell under the probe family, traced: weights, the engine's
    params, the reference and its control, the trace's kernels and the
    readers all go through the family file."""
    import time
    from unittest import mock

    from test_bench_drive import LIMIT, tiny_cell
    cell = tiny_cell()
    dense_model = cell.model
    cell.config.update(family="probe", activation="silu")
    assert cell.family is probe and cell.model["family"] == "probe"
    with mock.patch.object(tr, "read_xspace",
                           lambda path: _trace_with_kernels()):
        out = harness.run(cell, 2**31 + 29, 1.0, True, time.perf_counter(),
                          require_tpu=False, control=True)
    res = out["result"]
    assert res["correct"], res["checks"]
    assert out["diag"]["control_gap"] > LIMIT
    assert out["diag"]["control_correct"] is False
    assert {"shapes", "program_slot", "layer"} <= set(probe.CALLS)
    assert out["view"].trace["kernels"]["ffn.1"] == "probe_ffn"
    assert "dev.idle_share.sat" in res["metrics"]
    # the cost model counts the probe's layer as the dense one it copies
    n = len(probe.CALLS)
    assert (cost.forward_flops(cell.model, 8, 32, True)
            == cost.forward_flops(dense_model, 8, 32, True))
    assert probe.CALLS[n:] == ["layer_flops"]


@pytest.mark.parametrize("key, value", [("activation", "gelu"),
                                        ("d_ff", 7), ("family", "no/such")])
def test_family_keys_checked_against_program(probe, key, value):
    from test_bench_drive import tiny_cell
    cell = tiny_cell()
    cell.config.update(family="probe", activation="silu")
    harness.program_config(cell)
    cell.config[key] = value
    with pytest.raises(ValueError, match=key):
        harness.program_config(cell)
