"""The benchmark's yardsticks: FLOP and byte counts, traffic generation,
the peaks table and the checks made on BENCHMARK.json before a run."""
import copy
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path.insert(0, ROOT)

from bench import cost, spec, traffic  # noqa: E402
from bench.peaks import peaks  # noqa: E402


def _config(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


QWEN, DREAM = _config("qwen2-0.5b"), _config("dream-7b-l14")


def test_qwen_costs_by_hand():
    # attention 896*896*2 + 2*896*128, SwiGLU 3*896*4864
    assert cost.layer_params(QWEN) == 802_816 * 2 + 229_376 + 13_074_432
    sel = cost.select_call(QWEN, 512)
    assert sel["flops"] == 139_401_887_744          # 2*512*896*151936
    assert sel["bytes"] == 272_269_312 + 917_504 + 6_144
    att = cost.paged_attn_call(QWEN, 32, [512, 544])
    assert att["flops"] == 114_688 * (544 + 576)    # 4*32*14*64 per key
    assert att["bytes"] == (544 + 576) * 512 + 2 * 114_688
    assert cost.forward_flops(QWEN, 32, 512, True) == (
        22_900_899_840 + 1_497_366_528 + 8_712_617_984)


def test_dream_costs_by_hand():
    assert cost.layer_params(DREAM) == 233_046_016
    sel = cost.select_call(DREAM, 512)
    assert sel["flops"] == 2 * 512 * 3584 * 152_064
    assert sel["bytes"] == 3584 * 152_064 * 2 + 512 * 3584 * 2 + 512 * 12
    att = cost.paged_attn_call(DREAM, 32, [512])
    assert att["flops"] == 4 * 32 * 544 * 28 * 128
    assert att["bytes"] == 544 * 2 * 4 * 128 * 2 + 2 * 32 * 28 * 128 * 2


def test_min_seconds_takes_the_binding_roof():
    pk = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert cost.min_seconds({"flops": 100.0, "bytes": 1.0}, pk) == 1.0
    assert cost.min_seconds({"flops": 1.0, "bytes": 100.0}, pk) == 10.0


MIX = {"loop": "open", "rate_per_s": 20.0, "conf_threshold": 0.0,
       "max_tokens": {"median": 96, "sigma": 0.8, "min": 32, "max": 256},
       "pool": 4096, "lead_in_s": 3}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12_345])
def test_traffic_seed_orders_a_fixed_pool(seed):
    a, b = traffic.lengths(MIX, seed), traffic.lengths(MIX, seed)
    np.testing.assert_array_equal(a, b)
    other = traffic.lengths(MIX, seed + 1)
    assert not np.array_equal(a, other)
    np.testing.assert_array_equal(np.sort(a), np.sort(other))
    p = traffic.prompt(seed, 3, 512, 151_643)
    np.testing.assert_array_equal(p, traffic.prompt(seed, 3, 512, 151_643))
    assert p.shape == (512,) and p.min() >= 0 and p.max() < 151_643


@pytest.mark.parametrize("seed", [0, 2**31 + 12_345])
def test_open_loop_window_holds_the_same_work(seed):
    t, n = traffic.open_schedule(MIX, seed, 30.0)
    t2, n2 = traffic.open_schedule(MIX, seed, 30.0)
    np.testing.assert_array_equal(t, t2)
    np.testing.assert_array_equal(n, n2)
    u, m = traffic.open_schedule(MIX, seed + 1, 30.0)
    np.testing.assert_array_equal(t, u)      # the same send times
    assert not np.array_equal(n, m)          # in another order
    lead, win = t < 3, (t >= 3) & (t < 33)
    assert lead.sum() == 60 and win.sum() == 600 and len(t) == 660
    np.testing.assert_array_equal(np.sort(n[win]), np.sort(m[win]))
    assert np.all(np.diff(t) >= 0)


def test_traffic_draws():
    n = traffic.length_pool(MIX)
    assert n.min() == 32 and n.max() == 256
    assert 86 <= np.median(n) <= 106
    t, _ = traffic.open_schedule(MIX, 1, 300.0)
    gaps = np.diff(t[60:])
    assert abs(np.mean(gaps) - 1 / 20.0) < 0.05 / 20.0
    # Poisson: exponential gaps, coefficient of variation near 1
    assert 0.9 < np.std(gaps) / np.mean(gaps) < 1.1


def test_peaks_lookup():
    pk = peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_validates():
    spec.validate(_bench())


@pytest.mark.parametrize("breakage", [
    lambda b: b["end_to_end"][0].update(name="tok s"),
    lambda b: b["end_to_end"][0].update(unit="tokens per second"),
    lambda b: b["end_to_end"][0].update(unit="x" * 17),
    lambda b: b["workloads"][0].update(name="a/b"),
    lambda b: b["per_layer"][0].update(moves="not_a_metric"),
    lambda b: b["per_layer"][0].pop("workloads"),
    lambda b: b["per_layer"][0].update(workloads=["no.such.cell"]),
    lambda b: b["end_to_end"][1].update(workloads=[b["workloads"][0]["name"]])
    or b["per_layer"].append(dict(b["per_layer"][0], name="x.y",
                                  moves=b["end_to_end"][1]["name"],
                                  workloads=[b["workloads"][1]["name"]])),
])
def test_benchmark_json_refusals(breakage):
    b = copy.deepcopy(_bench())
    breakage(b)
    with pytest.raises(ValueError):
        spec.validate(b)
