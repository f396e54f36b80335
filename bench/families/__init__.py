"""Model families: the part of the benchmark that depends on the decoder
layer, one module each.

A configuration file names its family under ``"family"``; a file without
the key is ``dense``. The harness (``bench/harness.py``), the weights
(``bench/weights.py``), the plain reference (``bench/reference.py``), the
cost model (``bench/cost.py``) and the trace reduction (``bench/trace.py``)
take everything that depends on the layer from that module, so a new
architecture arrives as new files: ``bench/configs/<name>.json`` with its
``"family"``, and ``bench/families/<family>.py``.

A family module provides:

- ``KEYS``: the model keys it reads from a configuration file beyond
  ``bench.spec.MODEL_KEYS``. ``harness.program_config`` checks each of them
  against the program's ``ModelConfig``, as it checks the common ones.
- ``shapes(m) -> {leaf: (shape, kind)}``: the per-layer leaves for the
  model dict ``m``, stacked on axis 0 over ``m["n_layers"]``.
  ``bench.weights`` draws the kinds ``mat``, ``bias`` and ``gain``; a
  family with kinds of its own also provides ``leaf(key, shape, kind,
  dtype)``, which draws one such leaf.
- ``program_slot(w) -> dict``: the per-layer leaves of the flat weights
  ``w`` arranged as one slot of the serving program's params tree.
- ``layer(m, low, x, lw, past_k, past_v, past_ok, pos) -> (x, k, v)``: one
  decoder layer of the plain reference, in float32 at ``highest``, built
  from the helpers of ``bench.reference`` (``_mm``, which rounds through
  float8 where ``low`` asks for the control, ``_rmsnorm``, ``_rope``).
  x: (S, L, d); lw: this layer's leaves of ``shapes(m)``; past_k/v: (S, T,
  nkv, hd); past_ok: (T,) which past positions exist; pos: (L,).
- ``layer_flops(m, tokens)``: FLOPs of the matmul work that ``tokens``
  tokens do in one layer (the attention scores are ``bench.cost``'s).
- ``KERNELS`` (optional): kernel kind -> compiled regex over a TPU custom
  call's HLO text, added to ``bench.trace.KERNELS`` when a run of the
  family's cells reduces its trace, so that a reader file under
  ``bench/metrics/`` can find the family's own kernels.
"""
from __future__ import annotations

import importlib
import re

DEFAULT = "dense"
_NAME = re.compile(r"[a-z_][a-z0-9_]*")


def load(name: str):
    """The family module ``name`` (a module of this package)."""
    if not isinstance(name, str) or not _NAME.fullmatch(name):
        raise ValueError(f"bench: bad model family {name!r}")
    return importlib.import_module(f"{__name__}.{name}")


def of(m: dict):
    """The family of a configuration or model dict (its ``"family"``,
    ``dense`` where it has none)."""
    return load(m.get("family", DEFAULT))
