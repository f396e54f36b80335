"""Block-causal flash attention — Pallas TPU kernel.

TPU adaptation of the paper's student attention (DESIGN.md §4): the
block-causal mask is evaluated *tile-wise*. With MXU-aligned tiles
(block_q × block_k = 128×128 by default) a (q-tile, k-tile) pair is either

- fully visible   (k-block entirely before the q-tile's earliest CDLM block,
                   or bidirectional mode)        -> plain matmul, no select;
- fully hidden    (k-block entirely after the latest visible block)
                   -> tile skipped by the visibility predicate;
- boundary        -> per-element mask from broadcasted iotas.

The online-softmax accumulator (m, l, acc) lives in fp32 VMEM scratch; the
k-tile loop is the innermost ("arbitrary") grid dimension so the MXU stays
busy while VMEM streams KV tiles from HBM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _tile_visibility(qi, ki, *, block_q, block_k, mode, prompt_len,
                     block_size, window):
    """Per-element (block_q, block_k) visibility for tile (qi, ki)."""
    q = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    if mode == "bidirectional":
        vis = jnp.ones((block_q, block_k), bool)
    elif mode == "causal":
        vis = k <= q
    else:  # block_causal
        qb = jnp.where(q < prompt_len, -1, (q - prompt_len) // block_size)
        kb = jnp.where(k < prompt_len, -1, (k - prompt_len) // block_size)
        vis = kb <= qb
    if window is not None:
        if mode == "causal":
            vis = vis & (q - k < window)
        else:
            vis = vis & (jnp.abs(q - k) < window)
    return vis


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale, softcap, mode, prompt_len, block_size, window,
                  block_q, block_k, n_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale          # (block_q, d)
    k = k_ref[0].astype(jnp.float32)                  # (block_k, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    vis = _tile_visibility(qi, ki, block_q=block_q, block_k=block_k,
                           mode=mode, prompt_len=prompt_len,
                           block_size=block_size, window=window)
    s = jnp.where(vis, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)
    acc = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(ki == n_k - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def block_attention(q, k, v, *, mode: str = "block_causal",
                    prompt_len: int = 0, block_size: int = 1,
                    window: Optional[int] = None, scale: float = 1.0,
                    softcap: Optional[float] = None, block_q: int = 128,
                    block_k: int = 128, g: int = 1,
                    interpret: Optional[bool] = None):
    """q: (bh, L, d) — batch×q-heads flattened; k/v: (bh // g, L, d) — KV
    heads *not* expanded: query head ``h`` reads KV head ``h // g`` through
    the BlockSpec index map (in-kernel GQA head-group indexing), so the
    G-fold repeat never exists in HBM. L must be a multiple of the tile
    sizes (ops.py pads). Returns (bh, L, d).
    """
    bh, Lq, d = q.shape
    Lk = k.shape[1]
    assert Lq % block_q == 0 and Lk % block_k == 0, (Lq, Lk, block_q, block_k)
    assert bh == k.shape[0] * g, (bh, k.shape[0], g)
    n_q, n_k = Lq // block_q, Lk // block_k

    kernel = functools.partial(
        _flash_kernel, scale=scale, softcap=softcap, mode=mode,
        prompt_len=prompt_len, block_size=block_size, window=window,
        block_q=block_q, block_k=block_k, n_k=n_k)

    return pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // g, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, Lq, d), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(q, k, v)
