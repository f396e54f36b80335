"""``BENCHMARK.json``: loading, and the checks made before any run.

Names use only ``[A-Za-z0-9_.-]`` (first character a letter, digit or
``_``, at most 64); units those characters plus ``/`` and ``%``, at most
16. Every per-layer metric names one end-to-end metric it ``moves`` and a
``workloads`` list of cells that all report that metric.
"""
from __future__ import annotations

import json
import re

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
# the model keys every configuration file carries; a model family
# (``bench/families/``) reads its own ``KEYS`` besides
MODEL_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "vocab_size", "qkv_bias", "tie_embeddings", "rope_theta",
              "norm_eps", "mask_token_id", "eos_token_id")


def _name(v, what):
    if not isinstance(v, str) or not NAME.fullmatch(v):
        raise ValueError(f"BENCHMARK.json: bad {what} {v!r}")


def validate(b: dict) -> None:
    cells = {}
    for w in b["workloads"]:
        for k in ("name", "config", "traffic"):
            _name(w[k], f"workload {k}")
        if w["name"] in cells:
            raise ValueError(f"BENCHMARK.json: workload {w['name']} twice")
        cells[w["name"]] = w
    configs = {c["name"] for c in b["configs"]}
    for c in b["configs"]:
        _name(c["name"], "config name")
        for k in c["reduced"]:
            _name(k, "reduced key")
    for w in cells.values():
        if w["config"] not in configs:
            raise ValueError(f"BENCHMARK.json: {w['name']} names unknown "
                             f"config {w['config']!r}")
    names = set()
    e2e = {}
    for group in ("end_to_end", "per_layer"):
        for m in b[group]:
            _name(m["name"], "metric name")
            if m["name"] in names:
                raise ValueError(f"BENCHMARK.json: metric {m['name']} twice")
            names.add(m["name"])
            if not isinstance(m["unit"], str) or not UNIT.fullmatch(m["unit"]):
                raise ValueError(f"BENCHMARK.json: {m['name']}: bad unit "
                                 f"{m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                raise ValueError(f"BENCHMARK.json: {m['name']}: better must "
                                 "be lower or higher")
            for c in m.get("workloads", []):
                if c not in cells:
                    raise ValueError(f"BENCHMARK.json: {m['name']} lists "
                                     f"unknown cell {c!r}")
            if group == "end_to_end":
                e2e[m["name"]] = set(m.get("workloads", cells))
    for m in b["per_layer"]:
        if m.get("moves") not in e2e:
            raise ValueError(f"BENCHMARK.json: {m['name']} moves "
                             f"{m.get('moves')!r}, not an end-to-end metric")
        if "workloads" not in m:
            raise ValueError(f"BENCHMARK.json: {m['name']} has no workloads")
        for c in m["workloads"]:
            if c not in e2e[m["moves"]]:
                raise ValueError(f"BENCHMARK.json: {m['name']} lists {c}, "
                                 f"which does not report {m['moves']}")


def load(path: str) -> dict:
    with open(path) as f:
        b = json.load(f)
    validate(b)
    return b


def workload(b: dict, name: str) -> dict:
    for w in b["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")


def cell_metrics(b: dict, cell: str, group: str) -> list:
    """The ``group`` metrics (``end_to_end`` or ``per_layer``) that cell
    ``cell`` reports."""
    return [m for m in b[group] if cell in m.get("workloads", [cell])]
