"""Fused unembed + online-softmax candidate selection — Pallas TPU kernel.

The CDLM refinement step (paper §4.3, Alg. 1 line 11) needs exactly two
numbers per position: the argmax token of ``p_theta(x0|x_t)`` and its
probability. The baseline path materializes ``(b, L, V)`` logits in HBM
(``lm_head``), re-reads them for a full fp32 softmax, and reads them again
for the argmax/gather — at Dream/LLaDA vocabs (V ≳ 100k) that is several
times more HBM traffic than the whole cached attention pass. This kernel
streams vocab tiles of the unembedding matrix through VMEM the way
``kernels/xent`` does for the training loss: each grid step computes one
``(block_t × block_v)`` logit tile with a single MXU matmul and folds it
into flash-style running statistics

- ``m``  — running max logit,
- ``l``  — running sum of ``exp(logit - m)`` (rescaled on max updates),
- ``i``  — running argmax in global vocab coordinates
           (first-occurrence tie-break, matching ``jnp.argmax``),

so the only HBM writes are the ``(T,)`` candidate ids and ``(T,)``
confidences. The argmax logit *is* the running max, so its softmax
probability finalizes to ``1 / l`` — no second pass.

Rows whose canvas token is already finalized (``mask == 0``) get ``-inf``
confidence in-kernel, matching ``diffusion.confidence_and_candidates``
(unmasked positions are never re-finalized).

Grid: (T_tiles, V_tiles), V innermost ("arbitrary"). Supports gemma-style
final-logit softcap and bf16 hidden/weights with fp32 accumulation. Vocab
padding columns (``vpos >= v_total``) are masked to ``-inf`` in-kernel, so
any V works regardless of tile divisibility.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _select_kernel(h_ref, w_ref, mask_ref, cand_ref, conf_ref,
                   m_scr, l_scr, i_scr, *, block_t, block_v, n_v, v_total,
                   softcap):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        i_scr[...] = jnp.zeros_like(i_scr)

    h = h_ref[...].astype(jnp.float32)                    # (block_t, d)
    w = w_ref[...].astype(jnp.float32)                    # (d, block_v)
    logits = jax.lax.dot_general(h, w, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    vpos = vi * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_t, block_v), 1)
    logits = jnp.where(vpos < v_total, logits, -jnp.inf)

    m_prev = m_scr[...]
    tile_m = jnp.max(logits, axis=-1, keepdims=True)      # (block_t, 1)
    # first-occurrence argmax of the tile, in global vocab coordinates
    tile_i = jnp.min(jnp.where(logits == tile_m, vpos, 2**31 - 1),
                     axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, tile_m)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_new), 0.0)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(jnp.exp(logits - m_new),
                                              axis=-1, keepdims=True)
    # strict > keeps the earlier tile's index on cross-tile ties, matching
    # jnp.argmax's first-occurrence semantics over the full row
    i_scr[...] = jnp.where(tile_m > m_prev, tile_i, i_scr[...])
    m_scr[...] = m_new

    @pl.when(vi == n_v - 1)
    def _finalize():
        # the argmax logit is the running max, so softmax(conf) = 1/l
        conf = 1.0 / l_scr[...]
        conf_ref[...] = jnp.where(mask_ref[...] != 0, conf, -jnp.inf)
        cand_ref[...] = i_scr[...]


def select_forward(hidden, w, masked, *, v_total: Optional[int] = None,
                   softcap: Optional[float] = None, block_t: int = 128,
                   block_v: int = 512, interpret: Optional[bool] = None):
    """hidden: (T, d); w: (d, Vp); masked: (T,) int32 (0 = finalized row)
    -> (cand (T,) int32, conf (T,) fp32).

    T must be a multiple of block_t and Vp of block_v (ops.py pads);
    ``v_total`` is the true vocab size — columns at/after it are padding
    and masked to -inf in-kernel. The per-row operands travel as
    ``(T, 1)`` columns in ``(block_t, 1)`` blocks: Mosaic tiles a 1-D
    ``(block_t,)`` block at 128 while XLA lays a longer 1-D array out in
    one tile, so 1-D blocks fail to compile for the TPU at any T > 128."""
    T, d = hidden.shape
    Vp = w.shape[1]
    v_total = Vp if v_total is None else v_total
    assert T % block_t == 0 and Vp % block_v == 0, (T, Vp, block_t, block_v)
    assert v_total <= Vp
    n_t, n_v = T // block_t, Vp // block_v

    kernel = functools.partial(_select_kernel, block_t=block_t,
                               block_v=block_v, n_v=n_v, v_total=v_total,
                               softcap=softcap)
    cand, conf = pl.pallas_call(
        kernel,
        grid=(n_t, n_v),
        in_specs=[
            pl.BlockSpec((block_t, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, block_v), lambda i, j: (0, j)),
            pl.BlockSpec((block_t, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_t, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, 1), jnp.int32),
            jax.ShapeDtypeStruct((T, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_t, 1), jnp.float32),
            pltpu.VMEM((block_t, 1), jnp.float32),
            pltpu.VMEM((block_t, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(hidden, w, masked.reshape(T, 1))
    return cand[:, 0], conf[:, 0]
