"""Flash-decode Pallas kernel: active-block queries vs the KV cache.

TPU adaptation (DESIGN.md §4): a CDLM decode step is a B=32-token query
block against a long cache. We fold the GQA group dimension into the query
rows — per KV head the MXU sees a (B·G, hd) × (hd, block_k) matmul, so even
B=32 with G=8 fills a 256-row tile (vs 32 wasted-lane rows if G stayed a
broadcast dim). The cache length is dynamic: tiles entirely beyond
``cache_len`` are skipped (``pl.when``), the boundary tile is masked by
iota comparison.

The kernel returns *unnormalized* online-softmax partials (acc, m, l) so
the caller can combine them with the fresh in-block attention part (tiny,
B×B, done in jnp) — the same (num, denom, max) combination used by the
sequence-parallel sharded decode in ``repro.parallel``, so single-chip and
distributed paths share one correctness story.

Two cache layouts share the online-softmax body:

- :func:`decode_attention_partial` — dense per-lane ``(bKv, S, hd)``
  buffers, contiguous KV tiles;
- :func:`paged_decode_attention_partial` — a block-paged pool
  ``(Kv, n_pages, page, hd)`` shared across lanes. The grid's KV dimension
  walks each lane's *page table* instead of a contiguous buffer: the table
  (scalar-prefetched to SMEM) feeds the K/V BlockSpec index_map, so tile j
  DMAs pool page ``table[lane, j]``; table entries past the lane's
  ``cache_len`` (including unallocated ``-1`` slots, clamped to a valid DMA)
  are skipped with ``pl.when``. ``cache_len`` is per-lane — lanes in one
  batch decode at different block offsets (continuous batching).
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


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                   acc_scr, m_scr, l_scr, *, scale, softcap, window, g: int,
                   block_k: int, n_k: int):
    ki = pl.program_id(1)
    cache_len = len_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ki * block_k < cache_len)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale      # (BqG, hd)
        k = k_ref[0].astype(jnp.float32)              # (block_k, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        rows = s.shape[0]
        kpos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 1)
        vis = kpos < cache_len
        if window is not None:
            qpos = cache_len + jax.lax.broadcasted_iota(
                jnp.int32, (rows, block_k), 0) // g
            vis = vis & (qpos - kpos < window)
        s = jnp.where(vis, s, NEG_INF)

        m_prev = m_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_new), 0.0)
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _finalize():
        acc_ref[0] = acc_scr[...]
        m_ref[0] = m_scr[...]
        l_ref[0] = l_scr[...]


def decode_attention_partial(q, k_cache, v_cache, cache_len, *,
                             scale: float = 1.0,
                             softcap: Optional[float] = None,
                             window: Optional[int] = None, g: int = 1,
                             block_k: int = 128,
                             interpret: Optional[bool] = None):
    """q: (bKv, BqG, hd); cache: (bKv, S, hd); cache_len: scalar int32.

    Returns unnormalized partials (acc (bKv, BqG, hd), m (bKv, BqG, 1),
    l (bKv, BqG, 1)) over cache slots < cache_len."""
    bKv, BqG, hd = q.shape
    S = k_cache.shape[1]
    assert S % block_k == 0
    n_k = S // block_k
    lens = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (1,))

    kernel = functools.partial(_decode_kernel, scale=scale, softcap=softcap,
                               window=window, g=g, block_k=block_k, n_k=n_k)
    acc, m, l = pl.pallas_call(
        kernel,
        grid=(bKv, n_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, BqG, hd), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, BqG, hd), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, BqG, 1), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, BqG, 1), lambda b, j: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bKv, BqG, hd), jnp.float32),
            jax.ShapeDtypeStruct((bKv, BqG, 1), jnp.float32),
            jax.ShapeDtypeStruct((bKv, BqG, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((BqG, hd), jnp.float32),
            pltpu.VMEM((BqG, 1), jnp.float32),
            pltpu.VMEM((BqG, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(lens, q, k_cache, v_cache)
    return acc, m, l


# ---------------------------------------------------------------------------
# Paged variant
# ---------------------------------------------------------------------------
def _paged_decode_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, acc_ref,
                         m_ref, l_ref, acc_scr, m_scr, l_scr, *, scale,
                         softcap, window, g: int, page: int, n_t: int):
    bi = pl.program_id(0)
    ji = pl.program_id(2)
    cache_len = len_ref[bi]

    @pl.when(ji == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # page j of lane bi covers positions [j*page, (j+1)*page); a page that
    # starts at/after cache_len holds nothing visible — in particular every
    # unallocated (-1) table slot, since committed positions always have
    # pages. pl.when skips its compute entirely.
    @pl.when((ji * page < cache_len) & (pt_ref[bi, ji] >= 0))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale   # (BqG, hd)
        k = k_ref[0, 0].astype(jnp.float32)           # (page, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        rows = s.shape[0]
        kpos = ji * page + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page), 1)
        vis = kpos < cache_len
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        if window is not None:
            qpos = cache_len + jax.lax.broadcasted_iota(
                jnp.int32, (rows, page), 0) // g
            vis = vis & (qpos - kpos < window)
        s = jnp.where(vis, s, NEG_INF)

        m_prev = m_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_new), 0.0)
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ji == n_t - 1)
    def _finalize():
        acc_ref[0, 0] = acc_scr[...]
        m_ref[0, 0] = m_scr[...]
        l_ref[0, 0] = l_scr[...]


def paged_decode_attention_partial(q, k_pages, v_pages, page_table,
                                   cache_lens, *, scale: float = 1.0,
                                   softcap: Optional[float] = None,
                                   window: Optional[int] = None, g: int = 1,
                                   interpret: Optional[bool] = None):
    """q: (b, Kv, BqG, hd); pools: (Kv, n_pages, page, hd);
    page_table: (b, n_t) int32 (-1 = unallocated); cache_lens: (b,) int32
    per-lane valid prefix.

    Returns unnormalized partials (acc (b, Kv, BqG, hd), m (b, Kv, BqG, 1),
    l (b, Kv, BqG, 1)) over each lane's cache slots < cache_lens[lane]."""
    b, Kv, BqG, hd = q.shape
    n_pages, page = k_pages.shape[1], k_pages.shape[2]
    n_t = page_table.shape[1]
    pt = jnp.asarray(page_table, jnp.int32)
    lens = jnp.broadcast_to(jnp.asarray(cache_lens, jnp.int32), (b,))

    kernel = functools.partial(_paged_decode_kernel, scale=scale,
                               softcap=softcap, window=window, g=g,
                               page=page, n_t=n_t)

    def page_idx(bi, ki, ji, pt_ref, len_ref):
        # unallocated slots clamp to page 0: a valid DMA whose compute is
        # pl.when-skipped
        return (ki, jnp.maximum(pt_ref[bi, ji], 0), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, Kv, n_t),
        in_specs=[
            pl.BlockSpec((1, 1, BqG, hd),
                         lambda bi, ki, ji, pt_ref, len_ref: (bi, ki, 0, 0)),
            pl.BlockSpec((1, 1, page, hd), page_idx),
            pl.BlockSpec((1, 1, page, hd), page_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, BqG, hd),
                         lambda bi, ki, ji, pt_ref, len_ref: (bi, ki, 0, 0)),
            pl.BlockSpec((1, 1, BqG, 1),
                         lambda bi, ki, ji, pt_ref, len_ref: (bi, ki, 0, 0)),
            pl.BlockSpec((1, 1, BqG, 1),
                         lambda bi, ki, ji, pt_ref, len_ref: (bi, ki, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((BqG, hd), jnp.float32),
            pltpu.VMEM((BqG, 1), jnp.float32),
            pltpu.VMEM((BqG, 1), jnp.float32),
        ],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, Kv, BqG, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, Kv, BqG, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, Kv, BqG, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(pt, lens, q, k_pages, v_pages)
    return acc, m, l
