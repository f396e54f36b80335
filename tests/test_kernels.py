"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode).

Sweeps shapes and dtypes per the brief; hypothesis drives the geometry of
the block-causal mask for the attention kernel.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st
from _jaxpr_walk import paged_kernel_calls

from repro.kernels.block_attn import block_attention_ref, flash_block_attention
from repro.kernels.decode_attn import (
    decode_attention,
    decode_attention_ref,
    paged_decode_attention,
    paged_decode_attention_ref,
)
from repro.kernels.decode_attn.decode_attn import pages_per_step
from repro.kernels.xent import fused_xent, xent_ref


def _gqa_ref(q, k, v, **kw):
    b, L, Kv, G, hd = q.shape
    qr = q.transpose(0, 2, 3, 1, 4).reshape(b, Kv * G, L, hd)
    kr = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1)
    vr = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1)
    ref = block_attention_ref(qr, kr, vr, **kw)
    return ref.reshape(b, Kv, G, L, hd).transpose(0, 3, 1, 2, 4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("L,mode,P,B,win,cap", [
    (128, "block_causal", 32, 16, None, None),
    (200, "block_causal", 40, 8, None, None),
    (256, "causal", 0, 1, None, None),
    (160, "bidirectional", 0, 1, None, None),
    (256, "block_causal", 64, 32, 64, 50.0),
    (192, "causal", 0, 1, 96, None),
])
def test_block_attn_vs_oracle(L, mode, P, B, win, cap, dtype):
    key = jax.random.PRNGKey(0)
    b, Kv, G, hd = 2, 2, 3, 64
    q = jax.random.normal(key, (b, L, Kv, G, hd)).astype(dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, L, Kv, hd)).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, L, Kv, hd)).astype(dtype)
    out = flash_block_attention(q, k, v, mode=mode, prompt_len=P,
                                block_size=B, window=win, scale=0.125,
                                softcap=cap)
    ref = _gqa_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                   v.astype(jnp.float32), mode=mode, prompt_len=P,
                   block_size=B, window=win, scale=0.125, softcap=cap)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    assert float(jnp.max(jnp.abs(out - ref))) < tol


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 4), st.integers(1, 4), st.integers(0, 3))
def test_block_attn_property_geometry(nb, bs_pow, p_quarter):
    B = 2 ** bs_pow
    P = p_quarter * 16
    L = P + nb * B * 4
    key = jax.random.PRNGKey(L)
    q = jax.random.normal(key, (1, L, 1, 2, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, L, 1, 32))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, L, 1, 32))
    out = flash_block_attention(q, k, v, mode="block_causal", prompt_len=P,
                                block_size=B * 4, scale=0.2)
    ref = _gqa_ref(q, k, v, mode="block_causal", prompt_len=P,
                   block_size=B * 4, scale=0.2)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-4


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,Bq,Kv,G,clen,win", [
    (256, 32, 2, 4, 200, None),
    (256, 32, 2, 4, 0, None),
    (512, 16, 1, 8, 300, 128),
    (128, 8, 4, 1, 128, None),
    (384, 32, 2, 2, 37, None),
])
def test_decode_attn_vs_oracle(S, Bq, Kv, G, clen, win, dtype):
    b, hd = 2, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (b, Bq, Kv, G, hd)).astype(dtype)
    kc = jax.random.normal(ks[1], (b, S, Kv, hd)).astype(dtype)
    vc = jax.random.normal(ks[2], (b, S, Kv, hd)).astype(dtype)
    kb = jax.random.normal(ks[3], (b, Bq, Kv, hd)).astype(dtype)
    vb = jax.random.normal(ks[4], (b, Bq, Kv, hd)).astype(dtype)
    out = decode_attention(q, kc, vc, kb, vb, jnp.asarray(clen),
                           scale=0.125, window=win)
    ref = decode_attention_ref(
        q.astype(jnp.float32), kc.astype(jnp.float32),
        vc.astype(jnp.float32), kb.astype(jnp.float32),
        vb.astype(jnp.float32), clen, scale=0.125, window=win)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    assert float(jnp.max(jnp.abs(out - ref))) < tol


def _paged_inputs(key, b, Bq, Kv, G, hd, n_pages, page, n_t):
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (b, Bq, Kv, G, hd))
    kp = jax.random.normal(ks[1], (n_pages, page, Kv, hd))
    vp = jax.random.normal(ks[2], (n_pages, page, Kv, hd))
    kb = jax.random.normal(ks[3], (b, Bq, Kv, hd))
    vb = jax.random.normal(ks[4], (b, Bq, Kv, hd))
    return q, kp, vp, kb, vb


# 16 lanes at mixed offsets: empty, one page, one past a page boundary, a full
# table, and tables cut in chunks of pages_per_step (qwen2-like heads: 24
# pages in 2-3 whole chunks; dream-like heads: 22 pages, a short last chunk)
_LANES16 = (0, 32, 33, 768, 100, 31, 640, 0, 64, 65, 500, 767, 1, 320, 256,
            700)
_QWEN, _DREAM = (2, 7, 64), (4, 7, 128)


def _paged_table(lens, page, n_t, n_pages):
    """Scattered, non-monotone page assignment; unallocated tail slots -1."""
    perm = np.random.default_rng(0).permutation(n_pages)
    table = np.full((len(lens), n_t), -1, np.int32)
    for lane, ln in enumerate(lens):
        for j in range(-(-ln // page)):
            table[lane, j] = perm[lane * n_t + j]
    return jnp.asarray(table)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("page,n_t,lens,win,cap,heads", [
    (16, 5, (40, 32), None, None, (2, 4, 64)),
    (16, 5, (0, 16), None, None, (2, 4, 64)),  # an empty lane, a 1-page lane
    (32, 4, (100, 37), 48, None, (2, 4, 64)),  # boundary page + window
    (16, 3, (48, 48), None, 30.0, (2, 4, 64)),  # full tables + softcap
    (32, 24, _LANES16, None, None, _QWEN),
    (32, 24, _LANES16, 200, None, _QWEN),
    (32, 22, tuple(min(n, 704) for n in _LANES16), None, 30.0, _DREAM),
])
def test_paged_decode_attn_vs_oracle(page, n_t, lens, win, cap, heads,
                                     dtype):
    """Paged kernel walks scattered, partially-allocated page tables with
    per-lane cache lengths and matches the gather-based oracle."""
    (Kv, G, hd), b, Bq = heads, len(lens), 8
    n_pages = max(12, b * n_t)
    rng = jax.random.PRNGKey(page + n_t)
    q, kp, vp, kb, vb = _paged_inputs(rng, b, Bq, Kv, G, hd, n_pages, page,
                                      n_t)
    q, kp, vp = q.astype(dtype), kp.astype(dtype), vp.astype(dtype)
    kb, vb = kb.astype(dtype), vb.astype(dtype)
    table = _paged_table(lens, page, n_t, n_pages)
    clens = jnp.asarray(lens, jnp.int32)
    out = paged_decode_attention(q, kp, vp, kb, vb, table, clens,
                                 scale=0.125, window=win, softcap=cap)
    ref = paged_decode_attention_ref(
        q.astype(jnp.float32), kp.astype(jnp.float32),
        vp.astype(jnp.float32), kb.astype(jnp.float32),
        vb.astype(jnp.float32), table, clens, scale=0.125, window=win,
        softcap=cap)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    assert float(jnp.max(jnp.abs(out - ref))) < tol


def test_paged_decode_attn_lane_vmap_is_one_batched_call():
    """``jax.vmap`` over one-lane calls (the serving engine's lane vmap,
    shared pools) runs one kernel call over all lanes, with no loop around
    it, and equals the batched call."""
    b, Bq, (Kv, G, hd), page, n_t = 16, 8, _QWEN, 32, 24
    n_pages = b * n_t
    q, kp, vp, kb, vb = _paged_inputs(jax.random.PRNGKey(3), b, Bq, Kv, G,
                                      hd, n_pages, page, n_t)
    table = _paged_table(_LANES16, page, n_t, n_pages)
    clens = jnp.asarray(_LANES16, jnp.int32)

    def one(q1, kb1, vb1, t1, n1):
        return paged_decode_attention(q1[None], kp, vp, kb1[None], vb1[None],
                                      t1[None], n1, scale=0.125)[0]

    lanes = jax.vmap(one)
    calls = paged_kernel_calls(jax.make_jaxpr(lanes)(q, kb, vb, table,
                                                     clens))
    assert [shape for shape, _ in calls] == [(b, n_t)]
    assert not any("while" in outer for _, outer in calls)
    batched = paged_decode_attention(q, kp, vp, kb, vb, table, clens,
                                     scale=0.125)
    assert np.array_equal(np.asarray(lanes(q, kb, vb, table, clens)),
                          np.asarray(batched))


def test_paged_decode_attn_vmap_over_pools_matches_per_lane():
    """Pools batched with the lanes (one pool per mapped element) keep the
    kernel's own batching and match calls made one by one."""
    n, b, Bq, Kv, G, hd, page, n_t = 3, 2, 8, 2, 4, 64, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(5), n)
    ins = [_paged_inputs(k, b, Bq, Kv, G, hd, b * n_t, page, n_t)
           for k in ks]
    q, kp, vp, kb, vb = (jnp.stack(a) for a in zip(*ins))
    table = jnp.arange(b * n_t, dtype=jnp.int32).reshape(b, n_t)
    clens = jnp.asarray([[50, 17], [0, 64], [33, 1]], jnp.int32)
    fn = lambda *a: paged_decode_attention(*a, scale=0.125)  # noqa: E731
    out = jax.vmap(fn, in_axes=(0, 0, 0, 0, 0, None, 0))(
        q, kp, vp, kb, vb, table, clens)
    for i in range(n):
        ref = fn(q[i], kp[i], vp[i], kb[i], vb[i], table, clens[i])
        assert np.allclose(np.asarray(out[i]), np.asarray(ref), atol=1e-5)


def test_paged_decode_attn_matches_dense_on_contiguous_layout():
    """With an identity page table the paged kernel must reproduce the dense
    flash-decode kernel exactly when the dense cache tile is the paged
    kernel's chunk of pages (same tiles, same online-softmax order)."""
    b, Bq, Kv, G, hd = 2, 8, 2, 4, 64
    page, n_t = 16, 5
    q, kp, vp, kb, vb = _paged_inputs(jax.random.PRNGKey(7), b, Bq, Kv, G,
                                      hd, b * n_t, page, n_t)
    table = jnp.arange(b * n_t, dtype=jnp.int32).reshape(b, n_t)
    kc = kp.reshape(b, n_t * page, Kv, hd)
    vc = vp.reshape(b, n_t * page, Kv, hd)
    clen = 40
    chunk = page * pages_per_step(page, Kv, hd, kp.dtype.itemsize, n_t)
    dense = decode_attention(q, kc, vc, kb, vb, jnp.asarray(clen),
                             scale=0.125, block_k=chunk)
    paged = paged_decode_attention(q, kp, vp, kb, vb, table,
                                   jnp.full((b,), clen, jnp.int32),
                                   scale=0.125)
    assert np.array_equal(np.asarray(dense), np.asarray(paged))


@pytest.mark.parametrize("T,d,V", [(128, 64, 512), (200, 32, 1000),
                                   (64, 128, 593), (96, 48, 2048)])
def test_xent_vs_oracle(T, d, V):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    h = jax.random.normal(ks[0], (T, d)) * 0.5
    w = jax.random.normal(ks[1], (d, V)) * 0.1
    y = jax.random.randint(ks[2], (T,), 0, V)
    assert float(jnp.max(jnp.abs(fused_xent(h, w, y) - xent_ref(h, w, y)))) < 1e-4


def test_xent_grads_vs_oracle():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    h = jax.random.normal(ks[0], (64, 32)) * 0.5
    w = jax.random.normal(ks[1], (32, 640)) * 0.1
    y = jax.random.randint(ks[2], (64,), 0, 640)
    g1 = jax.grad(lambda h, w: fused_xent(h, w, y).mean(), (0, 1))(h, w)
    g2 = jax.grad(lambda h, w: xent_ref(h, w, y).mean(), (0, 1))(h, w)
    for a, b in zip(g1, g2):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5


def test_xent_bf16_inputs():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    h = (jax.random.normal(ks[0], (128, 64)) * 0.5).astype(jnp.bfloat16)
    w = (jax.random.normal(ks[1], (64, 512)) * 0.1).astype(jnp.bfloat16)
    y = jax.random.randint(ks[2], (128,), 0, 512)
    got = fused_xent(h, w, y)
    ref = xent_ref(h.astype(jnp.float32), w.astype(jnp.float32), y)
    assert float(jnp.max(jnp.abs(got - ref))) < 5e-2
