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

from bench import families

BF16 = 2


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
    hd, nq, nkv = m["head_dim"], m["n_heads"], m["n_kv_heads"]
    flops = bytes_ = 0.0
    for ctx in contexts:
        keys = ctx + block
        flops += 4.0 * block * keys * nq * hd
        bytes_ += keys * 2 * nkv * hd * BF16 + 2 * block * nq * hd * BF16
    return {"flops": flops, "bytes": bytes_}


def forward_flops(m: dict, tokens: int, ctx: int, unembed: bool) -> float:
    """FLOPs of ``tokens`` new positions of one sequence that attend to
    ``ctx`` earlier positions and to each other (all layers), with or
    without the unembedding. The layers' matmul FLOPs are the model
    family's (``layer_flops``)."""
    d, hd, nq, V, L = (m["d_model"], m["head_dim"], m["n_heads"],
                       m["vocab_size"], m["n_layers"])
    f = L * families.of(m).layer_flops(m, tokens)
    f += L * 4 * tokens * (ctx + tokens) * nq * hd
    if unembed:
        f += tokens * 2 * d * V
    return float(f)


def min_seconds(cost: Dict[str, float], pk: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(cost["flops"] / pk["bf16_flops_per_s"],
               cost["bytes"] / pk["hbm_bytes_per_s"])
