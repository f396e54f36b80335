"""Operations and bytes the algorithm needs, computed from shapes.

The accounting of the repository's analytic roofline model
(``roofline/ai_model.py`` ``step_cost``, paper App. B.4: matmul FLOPs =
2·m·n·k, a kernel reads its operands and writes its results once), kept
here so that no later change to the program moves the yardstick. It
counts what one call of the fused select and one call of the paged
decode-attention kernel need, and a forward's FLOPs: real rows and
vocabulary, the live lanes' real contexts, no padding. ``m`` is a
configuration file's model dict; bf16 operands (2 bytes).
"""
from __future__ import annotations

from typing import Dict, Iterable

BF16 = 2


def _dims(m: dict):
    return (m["d_model"], m["head_dim"], m["n_heads"], m["n_kv_heads"],
            m["d_ff"], m["vocab_size"], m["n_layers"])


def layer_params(m: dict) -> int:
    """Matmul weights of one decoder layer (projections + gated FFN)."""
    d, hd, nq, nkv, ff, _, _ = _dims(m)
    return d * nq * hd + 2 * d * nkv * hd + nq * hd * d + 3 * d * ff


def select_call(m: dict, rows: int) -> Dict[str, float]:
    """One fused unembed + select call over ``rows`` hidden rows: the
    (d, V) unembedding at the real V, the rows, the mask in and
    (candidate, confidence) out."""
    d, V = m["d_model"], m["vocab_size"]
    return {"flops": 2.0 * rows * d * V,
            "bytes": float(d * V * BF16 + rows * d * BF16 + rows * 12)}


def paged_attn_call(m: dict, block: int,
                    contexts: Iterable[int]) -> Dict[str, float]:
    """One paged decode-attention call (one layer) for the live lanes:
    each lane's ``block`` queries attend to its ``ctx`` cached positions
    and its own block. Keys and values are read once, queries read and
    outputs written once."""
    _, hd, nq, nkv, _, _, _ = _dims(m)
    flops = bytes_ = 0.0
    for ctx in contexts:
        keys = ctx + block
        flops += 4.0 * block * keys * nq * hd
        bytes_ += keys * 2 * nkv * hd * BF16 + 2 * block * nq * hd * BF16
    return {"flops": flops, "bytes": bytes_}


def forward_flops(m: dict, tokens: int, ctx: int, unembed: bool) -> float:
    """FLOPs of ``tokens`` new positions of one sequence that attend to
    ``ctx`` earlier positions and to each other (all layers), with or
    without the unembedding."""
    d, hd, nq, _, _, V, L = _dims(m)
    f = tokens * L * 2 * layer_params(m)
    f += L * 4 * tokens * (ctx + tokens) * nq * hd
    if unembed:
        f += tokens * 2 * d * V
    return float(f)


def min_seconds(cost: Dict[str, float], pk: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(cost["flops"] / pk["bf16_flops_per_s"],
               cost["bytes"] / pk["hbm_bytes_per_s"])
