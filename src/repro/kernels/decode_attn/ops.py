"""Jit'd wrapper: full CDLM decode-step attention = kernel partials over the
cache ⊕ in-block bidirectional part, combined by online-softmax merge.

``decode_attention`` reads a dense per-lane cache; ``paged_decode_attention``
reads a block-paged pool through per-lane page tables (and takes *per-lane*
cache lengths, since paged decode serves lanes at mixed block offsets).

Tuning: both ops take ``config=KernelConfig`` (see
:mod:`repro.kernels.tuning`). For the dense kernel ``block_k`` is the cache
tile; the paged kernel's chunk of pages follows from the pool's and the
page table's shapes (``decode_attn.pages_per_step``), so only
``interpret`` resolves from the table there. The legacy ``block_k``/
``interpret`` kwargs stay as deprecated pass-throughs."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import tuning
from repro.kernels.decode_attn.decode_attn import (
    NEG_INF,
    decode_attention_partial,
    paged_decode_attention_partial,
)


def softmax_combine(parts):
    """Merge [(acc, m, l), ...] unnormalized online-softmax partials.

    Shared by this kernel and the sequence-parallel sharded decode
    (repro.parallel.seq_decode)."""
    m = functools.reduce(jnp.maximum, [p[1] for p in parts])
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    acc = sum(p[0] * jnp.where(jnp.isfinite(p[1]), jnp.exp(p[1] - m_safe), 0.0)
              for p in parts)
    l = sum(p[2] * jnp.where(jnp.isfinite(p[1]), jnp.exp(p[1] - m_safe), 0.0)
            for p in parts)
    return acc / jnp.maximum(l, 1e-30)


def _block_partial(q, k_blk, v_blk, *, scale, softcap, window, g):
    """In-block (Bq×Bq) attention partials in plain jnp — tiny."""
    s = jnp.einsum("bqh,bkh->bqk", q.astype(jnp.float32),
                   k_blk.astype(jnp.float32)) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    if window is not None:
        BqG, Bq = q.shape[1], k_blk.shape[1]
        qpos = jnp.arange(BqG)[:, None] // g
        kpos = jnp.arange(Bq)[None, :]
        s = jnp.where(jnp.abs(qpos - kpos) < window, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bqk,bkh->bqh", p, v_blk.astype(jnp.float32))
    return acc, m, l


@functools.partial(
    jax.jit,
    static_argnames=("scale", "softcap", "window", "block_k", "interpret",
                     "config"))
def decode_attention(q, k_cache, v_cache, k_blk, v_blk, cache_len, *,
                     scale: float = 1.0, softcap: Optional[float] = None,
                     window: Optional[int] = None,
                     block_k: Optional[int] = None,
                     interpret: Optional[bool] = None,
                     config: Optional[tuning.KernelConfig] = None):
    """Model-layout decode attention.

    q: (b, Bq, Kv, G, hd); k/v_cache: (b, S, Kv, hd); k/v_blk: (b, Bq, Kv, hd);
    cache_len: scalar int32 — valid cache prefix. Returns (b, Bq, Kv, G, hd)
    in q's dtype (fp32 accumulation inside), like the jnp attention path.
    """
    b, Bq, Kv, G, hd = q.shape
    S = k_cache.shape[1]
    cfg = tuning.resolve(
        "decode_attn",
        config=tuning.merge_legacy(config, block_k=block_k,
                                   interpret=interpret),
        S=S)
    block_k, interpret = cfg.block_k, cfg.interpret
    if S % block_k != 0:
        # the kernel requires S to tile exactly; fall back to the largest
        # dividing tile so tuned configs never break odd cache lengths
        while S % block_k != 0:
            block_k //= 2
    qf = q.transpose(0, 2, 1, 3, 4).reshape(b * Kv, Bq * G, hd)
    kcf = k_cache.transpose(0, 2, 1, 3).reshape(b * Kv, S, hd)
    vcf = v_cache.transpose(0, 2, 1, 3).reshape(b * Kv, S, hd)
    kbf = k_blk.transpose(0, 2, 1, 3).reshape(b * Kv, Bq, hd)
    vbf = v_blk.transpose(0, 2, 1, 3).reshape(b * Kv, Bq, hd)

    cache_part = decode_attention_partial(
        qf, kcf, vcf, cache_len, scale=scale, softcap=softcap, window=window,
        g=G, block_k=block_k, interpret=interpret)
    blk_part = _block_partial(qf, kbf, vbf, scale=scale, softcap=softcap,
                              window=window, g=G)
    out = softmax_combine([cache_part, blk_part])
    return out.reshape(b, Kv, Bq, G, hd).transpose(0, 2, 1, 3, 4).astype(
        q.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "softcap", "window", "interpret", "config"))
def paged_decode_attention(q, k_pages, v_pages, k_blk, v_blk, page_table,
                           cache_lens, *, scale: float = 1.0,
                           softcap: Optional[float] = None,
                           window: Optional[int] = None,
                           interpret: Optional[bool] = None,
                           config: Optional[tuning.KernelConfig] = None):
    """Model-layout decode attention over a block-paged KV pool.

    q: (b, Bq, Kv, G, hd); k/v_pages: (n_pages, page, Kv, hd) pools shared
    across lanes; k/v_blk: (b, Bq, Kv, hd) fresh in-block KV;
    page_table: (b, n_tables) int32 (-1 = unallocated); cache_lens: scalar
    or (b,) int32 — per-lane valid cache prefix. Returns (b, Bq, Kv, G, hd)
    in q's dtype.

    Under ``jax.vmap`` with lane-shared pools (the serving engine's lane
    vmap) the mapped lanes fold into ``b``: one kernel call for all lanes,
    not one per lane.
    """
    cfg = tuning.resolve(
        "decode_attn",
        config=tuning.merge_legacy(config, interpret=interpret),
        S=page_table.shape[1] * k_pages.shape[1])
    attend = jax.custom_batching.custom_vmap(functools.partial(
        _paged_attention, scale=scale, softcap=softcap, window=window,
        interpret=cfg.interpret))

    @attend.def_vmap
    def _fold_lanes(n, in_batched, *args):
        if in_batched[1] or in_batched[2]:
            # per-lane pools: the kernel's own batching, one call per lane
            axes = [0 if bt else None for bt in in_batched]
            return jax.vmap(attend.fun, in_axes=axes)(*args), True
        k_pages, v_pages = args[1:3]
        q, k_blk, v_blk, page_table, cache_lens = [
            a if bt else jnp.broadcast_to(a, (n, *a.shape))
            for a, bt in zip(args[:1] + args[3:],
                             in_batched[:1] + in_batched[3:])]
        b = q.shape[1]
        fold = lambda a: a.reshape(n * b, *a.shape[2:])  # noqa: E731
        lens = jnp.broadcast_to(cache_lens.reshape(n, -1), (n, b))
        out = attend(fold(q), k_pages, v_pages, fold(k_blk), fold(v_blk),
                     fold(page_table), lens.reshape(n * b))
        return out.reshape(n, b, *out.shape[1:]), True

    return attend(q, k_pages, v_pages, k_blk, v_blk, page_table,
                  jnp.asarray(cache_lens, jnp.int32))


def _paged_attention(q, k_pages, v_pages, k_blk, v_blk, page_table,
                     cache_lens, *, scale, softcap, window, interpret):
    b, Bq, Kv, G, hd = q.shape
    qf = q.transpose(0, 2, 1, 3, 4).reshape(b, Kv, Bq * G, hd)
    kp = k_pages.transpose(2, 0, 1, 3)        # (Kv, n_pages, page, hd)
    vp = v_pages.transpose(2, 0, 1, 3)
    kbf = k_blk.transpose(0, 2, 1, 3).reshape(b * Kv, Bq, hd)
    vbf = v_blk.transpose(0, 2, 1, 3).reshape(b * Kv, Bq, hd)

    acc, m, l = paged_decode_attention_partial(
        qf, kp, vp, page_table, cache_lens, scale=scale, softcap=softcap,
        window=window, g=G, interpret=interpret)
    cache_part = (acc.reshape(b * Kv, Bq * G, hd),
                  m.reshape(b * Kv, Bq * G, 1),
                  l.reshape(b * Kv, Bq * G, 1))
    blk_part = _block_partial(qf.reshape(b * Kv, Bq * G, hd), kbf, vbf,
                              scale=scale, softcap=softcap, window=window,
                              g=G)
    out = softmax_combine([cache_part, blk_part])
    return out.reshape(b, Kv, Bq, G, hd).transpose(0, 2, 1, 3, 4).astype(
        q.dtype)
