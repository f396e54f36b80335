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
  ``(Kv, n_pages, page, hd)`` shared across lanes, left in HBM. One grid
  step per lane walks its *page table* (scalar-prefetched to SMEM) in
  chunks of :func:`pages_per_step` pages, all KV heads at once: each page
  of a chunk is its own DMA into a double-buffered VMEM chunk, the next
  chunk (or the next lane's first) in flight while one is computed. Pages
  at or past the lane's ``cache_len`` — unallocated ``-1`` slots among
  them — are neither fetched nor computed. ``cache_len`` is per-lane: lanes
  in one batch decode at different block offsets (continuous batching).
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
#: K+V bytes one grid step of the paged kernel moves (one lane, all KV heads,
#: ``pages_per_step`` pages): large enough that the per-step fixed cost is
#: small against the step's DMA and matmuls, small enough that the
#: double-buffered chunk and its f32 scores sit well inside scoped VMEM.
KV_STEP_BYTES = 512 * 1024


def _lanes(hd: int) -> int:
    """``hd`` rounded up to whole 128-wide lane tiles: the width a page row
    takes in memory, and the width the kernel's DMAs move."""
    return -(-hd // 128) * 128


def pages_per_step(page: int, Kv: int, hd: int, itemsize: int,
                   n_t: int) -> int:
    """Pages of one lane's table a grid step covers, from the shapes alone:
    the fewest chunks of at most :data:`KV_STEP_BYTES` of K+V (all KV heads)
    that cover the table, cut evenly so the last chunk computes little past
    the table's end."""
    per_page = 2 * Kv * page * _lanes(hd) * itemsize
    n_c = -(-n_t // max(1, KV_STEP_BYTES // per_page))
    return -(-n_t // n_c)


def _paged_decode_kernel(pt_ref, len_ref, q_ref, k_hbm, v_hbm, acc_ref,
                         m_ref, l_ref, k_buf, v_buf, sems, slot_ref, *,
                         scale, softcap, window, g: int, page: int,
                         pps: int):
    b, n_t = pt_ref.shape
    Kv, hd = k_buf.shape[1], q_ref.shape[3]
    chunk = pps * page
    bi = pl.program_id(0)
    cache_len = len_ref[bi]

    def n_pages(lane):
        # pages holding a position < cache_len: the rest, -1 slots included,
        # are neither fetched nor computed
        return jnp.minimum((len_ref[lane] + page - 1) // page, n_t)

    def dma(lane, c, slot, i):
        # page i of chunk c, every KV head: one strided copy each for K, V
        pid = jnp.maximum(pt_ref[lane, c * pps + i], 0)
        return (pltpu.make_async_copy(k_hbm.at[:, pid], k_buf.at[slot, :, i],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[:, pid], v_buf.at[slot, :, i],
                                      sems.at[1, slot]))

    def for_chunk(lane, c, slot, fn):
        n = jnp.clip(n_pages(lane) - c * pps, 0, pps)

        def body(i, carry):
            for cp in dma(lane, c, slot, i):
                fn(cp)
            return carry
        jax.lax.fori_loop(0, n, body, 0)

    def start(lane, c, slot):
        for_chunk(lane, c, slot, lambda cp: cp.start())

    # The chunks of all lanes stream through two VMEM slots: while one is
    # computed the next is in flight, the next lane's first chunk included.
    # Invariant at each step's start: the lane's chunk 0 (if it has one) is
    # in flight in slot ``slot_ref[0]``.
    @pl.when(bi == 0)
    def _first():
        slot_ref[0] = 0
        start(0, 0, 0)

    m_ref[0] = jnp.full(m_ref.shape[1:], -jnp.inf, jnp.float32)
    l_ref[0] = jnp.zeros(l_ref.shape[1:], jnp.float32)
    acc_ref[0] = jnp.zeros(acc_ref.shape[1:], jnp.float32)
    n_c = (n_pages(bi) + pps - 1) // pps

    def prefetch_next_lane(slot):
        @pl.when(bi + 1 < b)
        def _():
            start(bi + 1, 0, slot)

    @pl.when(n_c == 0)
    def _empty():
        prefetch_next_lane(slot_ref[0])

    def chunk_body(c, slot):
        nxt = 1 - slot

        @pl.when(c + 1 < n_c)
        def _():
            start(bi, c + 1, nxt)

        @pl.when(c + 1 == n_c)
        def _():
            prefetch_next_lane(nxt)

        for_chunk(bi, c, slot, lambda cp: cp.wait())

        rows = q_ref.shape[2]
        kpos = c * chunk + jax.lax.broadcasted_iota(jnp.int32, (rows, chunk),
                                                    1)
        vis = kpos < cache_len
        if window is not None:
            qpos = cache_len + jax.lax.broadcasted_iota(
                jnp.int32, (rows, chunk), 0) // g
            vis = vis & (qpos - kpos < window)
        # rows of pages not fetched this chunk hold a stale chunk's data:
        # their scores are masked, and their values zeroed so 0 * stale
        # never reaches the sum
        vrow = (c * chunk + jax.lax.broadcasted_iota(
            jnp.int32, (chunk, 1), 0)) < cache_len

        def head(h, carry):
            q = q_ref[0, h].astype(jnp.float32) * scale   # (BqG, hd)
            k = k_buf[slot, h, :, :, :hd].astype(jnp.float32).reshape(
                chunk, hd)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(vis, s, NEG_INF)

            m_prev = m_ref[0, h]
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new)
            alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_new),
                              0.0)
            m_ref[0, h] = m_new
            l_ref[0, h] = l_ref[0, h] * alpha + jnp.sum(p, axis=-1,
                                                        keepdims=True)
            v = jnp.where(vrow, v_buf[slot, h, :, :, :hd].astype(
                jnp.float32).reshape(chunk, hd), 0.0)
            acc_ref[0, h] = acc_ref[0, h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, Kv, head, 0)
        return nxt

    slot_ref[0] = jax.lax.fori_loop(0, n_c, chunk_body, slot_ref[0])


def paged_decode_attention_partial(q, k_pages, v_pages, page_table,
                                   cache_lens, *, scale: float = 1.0,
                                   softcap: Optional[float] = None,
                                   window: Optional[int] = None, g: int = 1,
                                   interpret: Optional[bool] = None):
    """q: (b, Kv, BqG, hd); pools: (Kv, n_pages, page, hd);
    page_table: (b, n_t) int32 (-1 = unallocated); cache_lens: (b,) int32
    per-lane valid prefix.

    One grid step per lane walks its table in chunks of
    :func:`pages_per_step` pages, all KV heads at once, each page DMA'd
    from the pool (left in HBM) into a double-buffered VMEM chunk; the next
    chunk, or the next lane's first, is in flight while one is computed.

    Returns unnormalized partials (acc (b, Kv, BqG, hd), m (b, Kv, BqG, 1),
    l (b, Kv, BqG, 1)) over each lane's cache slots < cache_lens[lane]."""
    b, Kv, BqG, hd = q.shape
    page = k_pages.shape[2]
    n_t = page_table.shape[1]
    pps = pages_per_step(page, Kv, hd, k_pages.dtype.itemsize, n_t)
    # a DMA moves whole lane tiles: rows narrower than 128 are padded in
    # the pools (fused into the caller's transpose) and sliced in VMEM
    pad = [(0, 0)] * 3 + [(0, _lanes(hd) - hd)]
    k_pages, v_pages = jnp.pad(k_pages, pad), jnp.pad(v_pages, pad)
    pt = jnp.asarray(page_table, jnp.int32)
    lens = jnp.broadcast_to(jnp.asarray(cache_lens, jnp.int32), (b,))

    kernel = functools.partial(_paged_decode_kernel, scale=scale,
                               softcap=softcap, window=window, g=g,
                               page=page, pps=pps)
    lane = lambda bi, pt_ref, len_ref: (bi, 0, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, Kv, BqG, hd), lane),
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        out_specs=[
            pl.BlockSpec((1, Kv, BqG, hd), lane),
            pl.BlockSpec((1, Kv, BqG, 1), lane),
            pl.BlockSpec((1, Kv, BqG, 1), lane),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, Kv, pps, page, _lanes(hd)), k_pages.dtype),
            pltpu.VMEM((2, Kv, pps, page, _lanes(hd)), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
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
        # lanes run in order: each step's DMAs run ahead into the next's
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=resolve_interpret(interpret),
    )(pt, lens, q, k_pages, v_pages)
    return acc, m, l
