"""The one traffic generator. A mix is a data file (``bench/traffic/*.json``)
of parameters; nothing here knows a mix by name.

Keys of a mix:

- ``loop``: ``"closed"`` (``clients`` callers, each sends its next request
  when the last one has finished) or ``"open"`` (Poisson arrivals at
  ``rate_per_s``, sent on schedule whatever the server does: the lead-in
  and the window each get ``rate_per_s`` times their length arrivals,
  placed as a Poisson process given its count places them, uniformly;
  ``clients``, optional, caps the requests waiting or in flight, 64 when
  not given);
- ``conf_threshold``: the per-request confidence threshold tau;
- ``check_every`` (optional): every ``check_every``-th request asks for
  tau = 0 instead, so that each of its blocks is final after one forward
  and every token it is served can be checked against the reference
  whatever order the other lanes finalize theirs in;
- ``max_tokens``: ``{"median", "sigma", "min", "max"}`` of a log-normal
  draw, rounded and clipped;
- ``pool``: how many lengths the fixed pool holds;
- ``lead_in_s``, ``lead_in_steps`` (optional): the mix's own traffic runs
  this many seconds, and at least this many engine steps, before the
  window opens.

Every seed gets the same pool of lengths (drawn from a fixed seed), in its
own order, so that a seed changes the order of the work and not its
amount; in the open loop the arrival times and the lengths sent in the
window are the same set under every seed. Prompt ids and weights come
from the seed itself.
"""
from __future__ import annotations

import json
import os

import numpy as np

POOL_SEED = 20_251_119  # fixed: the pool is the same for every run seed


def load(bench_dir: str, name: str) -> dict:
    with open(os.path.join(bench_dir, "traffic", name + ".json")) as f:
        return json.load(f)


def _pool_rng(tag: int):
    return np.random.default_rng([POOL_SEED, tag])


def length_pool(mix: dict) -> np.ndarray:
    """The fixed pool of ``max_tokens`` values (same for every seed)."""
    d = mix["max_tokens"]
    z = _pool_rng(1).standard_normal(mix["pool"])
    n = np.rint(d["median"] * np.exp(d["sigma"] * z))
    return np.clip(n, d["min"], d["max"]).astype(np.int64)


def lengths(mix: dict, seed: int) -> np.ndarray:
    """This seed's order of the length pool (closed loop)."""
    return np.random.default_rng([seed, 1]).permutation(length_pool(mix))


def open_schedule(mix: dict, seed: int, seconds: float):
    """``(times, lengths)`` of the open loop: send times in seconds from
    the start of the lead-in, and each request's ``max_tokens``. The
    lead-in and the window hold fixed arrival times and fixed sets of
    lengths; the seed orders the lengths within each."""
    rate, lead = mix["rate_per_s"], mix["lead_in_s"]
    n_lead, n_win = round(rate * lead), round(rate * seconds)
    times = np.concatenate([
        lead * np.sort(_pool_rng(2).random(n_lead)),
        lead + seconds * np.sort(_pool_rng(3).random(n_win))])
    pool = length_pool(mix)
    rng = np.random.default_rng([seed, 1])
    sizes = np.concatenate([rng.permutation(pool[:n_lead]),
                            rng.permutation(pool[n_lead:n_lead + n_win])])
    return times, sizes


def tau(mix: dict, i: int) -> float:
    """Request ``i``'s confidence threshold."""
    k = mix.get("check_every")
    return 0.0 if k and i % k == k - 1 else float(mix["conf_threshold"])


def prompt(seed: int, i: int, prompt_len: int, id_max: int) -> np.ndarray:
    """Request ``i``'s prompt: ``prompt_len`` ids in ``[0, id_max)``."""
    return np.random.default_rng([seed, 3, i]).integers(
        0, id_max, prompt_len, dtype=np.int32)
