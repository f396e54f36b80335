"""Plain reference of the served model, and the check that decides
``correct``.

A dense GQA transformer with QKV bias, RoPE, RMSNorm and a SwiGLU MLP
(Qwen2 report, arXiv:2407.10671; Dream-7B shares the backbone), written
here from the published equations in ``jax.numpy`` and float32 at
``highest`` matmul precision. It imports nothing of the serving program:
it reads the benchmark's own weights (``bench/weights.py``) and the model
numbers of the configuration file.

Under CDLM's block-causal mask the prompt attends to itself, and a block
attends to the prompt, every earlier block and the whole of itself; so a
block's logits need no mask, only the keys and values of what came before.
:func:`replay` walks the sampled requests through their blocks the way
the serving algorithm decodes them (greedy threshold refinement: every
masked position whose confidence reaches tau, and at least the most
confident one, takes the served token), and at each finalized position
reads how far the served token's logit lies below the best logit of the
reference.

The control (``control=True``) runs the same model with weights and matmul
inputs rounded through float8 e4m3 (per-tensor and per-row scales) in the
same contexts, and reads the same gap for the token it puts first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


def _fp8(a, axis):
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, low: bool):
    w = w.astype(jnp.float32)
    if low:
        x, w = _fp8(x, -1), _fp8(w, None)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rmsnorm(x, g, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * g.astype(jnp.float32))


def _rope(x, pos, theta):
    """x: (S, L, H, hd); pos: (L,). Rotates the two halves of each head."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv          # (L, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(m, low, x, lw, past_k, past_v, past_ok, pos):
    """One decoder layer. x: (S, L, d) fp32; past_k/v: (S, T, nkv, hd);
    past_ok: (T,) which past positions exist. Returns (x, k, v)."""
    S, L, _ = x.shape
    nq, nkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    h = _rmsnorm(x, lw["norm1"], m["norm_eps"])
    q = _mm(h, lw["wq"], low)
    k = _mm(h, lw["wk"], low)
    v = _mm(h, lw["wv"], low)
    if "bq" in lw:
        q = q + lw["bq"].astype(jnp.float32)
        k = k + lw["bk"].astype(jnp.float32)
        v = v + lw["bv"].astype(jnp.float32)
    q = _rope(q.reshape(S, L, nq, hd), pos, m["rope_theta"])
    k = _rope(k.reshape(S, L, nkv, hd), pos, m["rope_theta"])
    v = v.reshape(S, L, nkv, hd)
    kk = jnp.concatenate([past_k, k], 1)
    vv = jnp.concatenate([past_v, v], 1)
    ok = jnp.concatenate([past_ok, jnp.ones((L,), bool)])
    g = nq // nkv
    qg = q.reshape(S, L, nkv, g, hd)
    s = jnp.einsum("slkgh,stkh->skglt", qg, kk, precision=HIGHEST)
    s = jnp.where(ok, s * hd ** -0.5, -jnp.inf)
    p = jax.nn.softmax(s, -1)
    o = jnp.einsum("skglt,stkh->slkgh", p, vv, precision=HIGHEST)
    x = x + _mm(o.reshape(S, L, nq * hd), lw["wo"], low)
    h = _rmsnorm(x, lw["norm2"], m["norm_eps"])
    a = _mm(h, lw["wi_gate"], low)
    x = x + _mm(jax.nn.silu(a) * _mm(h, lw["wi_up"], low), lw["wo_mlp"], low)
    return x, k, v


LAYER_KEYS = ("norm1", "norm2", "wq", "wk", "wv", "wo", "bq", "bk", "bv",
              "wi_gate", "wi_up", "wo_mlp")


def _stack(m, low, w, x, kbuf, vbuf, past_len, pos):
    """All layers, one at a time (``lax.scan``), each upcast on its own.
    kbuf/vbuf: (n_layers, S, T, nkv, hd) past keys/values, valid below
    ``past_len``. Returns (x, new k, new v) with k/v stacked by layer."""
    layers = {k: w[k] for k in LAYER_KEYS if k in w}
    past_ok = jnp.arange(kbuf.shape[2]) < past_len

    def body(x, xs):
        lw, pk, pv = xs
        x, k, v = _layer(m, low, x, lw, pk, pv, past_ok, pos)
        return x, (k, v)

    x, (k, v) = jax.lax.scan(body, x, (layers, kbuf, vbuf))
    return x, k, v


def _embed(w, tokens):
    return jnp.take(w["tok"], tokens, axis=0).astype(jnp.float32)


def _logits(m, low, w, x):
    h = _rmsnorm(x, w["final_norm"], m["norm_eps"])
    u = w["tok"].T if m["tie_embeddings"] else w["head"]
    return _mm(h, u, low)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _prefill(mkey, low, w, prompts, kbuf, vbuf):
    m = dict(mkey)
    P = prompts.shape[1]
    empty = jnp.zeros(kbuf.shape[:2] + (0,) + kbuf.shape[3:], jnp.float32)
    _, k, v = _stack(m, low, w, _embed(w, prompts), empty, empty, 0,
                     jnp.arange(P))
    kbuf = jax.lax.dynamic_update_slice_in_dim(kbuf, k, 0, 2)
    vbuf = jax.lax.dynamic_update_slice_in_dim(vbuf, v, 0, 2)
    return kbuf, vbuf


@functools.partial(jax.jit, static_argnums=(0, 1))
def _block(mkey, low, w, block, kbuf, vbuf, start):
    """Logits of one block at canvas offset ``start`` and its keys/values."""
    m = dict(mkey)
    B = block.shape[1]
    x, k, v = _stack(m, low, w, _embed(w, block), kbuf, vbuf, start,
                     start + jnp.arange(B))
    return _logits(m, low, w, x), k, v


@jax.jit
def _commit(kbuf, vbuf, k, v, start):
    return (jax.lax.dynamic_update_slice_in_dim(kbuf, k, start, 2),
            jax.lax.dynamic_update_slice_in_dim(vbuf, v, start, 2))


@jax.jit
def _read(logits, served):
    """Per position: gap of ``served`` below the row max, the top token and
    its softmax probability (the decoder's confidence)."""
    top = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, served[..., None], -1)[..., 0]
    conf = 1.0 / jnp.sum(jnp.exp(logits - top[..., None]), -1)
    return top - got, jnp.argmax(logits, -1), conf


def replay(m: dict, w: dict, prompts, served, n_blocks, taus, *,
           block_size: int, slack: float, control: bool = False) -> dict:
    """Check the served tokens of ``S`` requests against the reference.

    prompts: (S, P) int; served: (S, G) int, the tokens each request was
    served (only its first ``n_blocks[i]`` blocks are read); taus: (S,)
    confidence thresholds.

    Each block is decoded again the way the server decodes it, with the
    served tokens put in: at every step the masked positions whose
    confidence reaches tau, and the most confident one, are finalized,
    except where the best candidate is the [MASK] id itself, which leaves
    the position masked.
    Where two confidences lie within ``slack`` (in log-probability) of
    each other, program and reference may rank them apart, and one
    different token changes the logits of its neighbours; so the gap
    read there is the least over those positions, and the rest of that
    block is not read: every gap read is of a token whose context the
    reference knows. The blocks' keys and values are then committed from
    the served tokens, so every block starts from the exact context.

    Returns ``{"gap": widest gap of a served token's logit below the
    reference's best, "tokens": tokens read, "blocks": widest gap per
    block read, "tau_reads_by_step": tokens read of requests with tau > 0,
    by the refinement step of their block at which they were finalized}``
    and, with ``control``, ``"control_gap"``: the same for
    the tokens the float8 control puts first at the same positions and
    contexts."""
    mkey = tuple(sorted(m.items()))
    mask_id = m["mask_token_id"]
    prompts = np.asarray(prompts, np.int32)
    served = np.asarray(served, np.int32)
    n_blocks = np.asarray(n_blocks)
    taus = np.asarray(taus, np.float32)
    S, P = prompts.shape
    B = block_size
    shape = (m["n_layers"], S, P + served.shape[1], m["n_kv_heads"],
             m["head_dim"])
    models = [False] + ([True] if control else [])
    bufs = {low: _prefill(mkey, low, w, jnp.asarray(prompts),
                          jnp.zeros(shape, jnp.float32),
                          jnp.zeros(shape, jnp.float32)) for low in models}
    widest = widest_ctrl = 0.0
    count, per_block = 0, []
    by_step = np.zeros((B,), np.int64)
    rows = np.arange(S)
    for blk in range(int(n_blocks.max())):
        start = P + blk * B
        final = served[:, blk * B:(blk + 1) * B]
        fin = jnp.asarray(final)
        state = np.full((S, B), mask_id, np.int32)
        reading = n_blocks > blk
        block_gap = np.zeros((S,))
        for it in range(B):
            masked = (state == mask_id) & reading[:, None]
            if not masked.any():
                break
            x = jnp.asarray(state)
            ref = _block(mkey, False, w, x, *bufs[False], start)[0]
            gap, top, conf = (np.asarray(a) for a in _read(ref, fin))
            if control:
                low = _block(mkey, True, w, x, *bufs[True], start)[0]
                cgap = np.asarray(_read(ref, _read(low, fin)[1])[0])
            logc = np.where(masked, np.log(conf), -np.inf)
            near = masked & (logc >= logc.max(-1, keepdims=True) - slack)
            above = masked & (conf >= taus[:, None])
            tied = (near & ~above).sum(-1) > 1
            top1 = np.zeros_like(masked)
            top1[rows, np.argmax(logc, -1)] = True
            sel = masked & (above | (top1 & ~tied[:, None]))
            # a position whose best candidate is [MASK] itself stays masked
            # in the server's loop and is decided again at the next step
            sel &= (top != mask_id) | (final == mask_id)
            g = np.where(sel, gap, 0.0).max(-1)
            amb = near & ~above & tied[:, None]
            g = np.maximum(g, np.where(tied, np.where(amb, gap, np.inf)
                                       .min(-1), 0.0))
            block_gap = np.maximum(block_gap, np.where(reading, g, 0.0))
            read = sel.sum(-1) + tied
            count += int(read.sum())
            by_step[it] += int(read[taus > 0].sum())
            if control:
                c = np.where(sel, cgap, 0.0).max(-1)
                c = np.maximum(c, np.where(tied, np.where(amb, cgap, np.inf)
                                           .min(-1), 0.0))
                widest_ctrl = max(widest_ctrl, float(np.where(
                    reading, c, 0.0).max()))
            state = np.where(sel, final, state)
            reading = reading & ~tied
        per_block.extend(block_gap[n_blocks > blk].tolist())
        widest = max(widest, float(block_gap.max()))
        for low in models:  # commit: keys/values of the served block
            _, k, v = _block(mkey, low, w, fin, *bufs[low], start)
            bufs[low] = _commit(*bufs[low], k, v, start)
    res = {"gap": widest, "tokens": count, "blocks": per_block,
           "tau_reads_by_step": by_step.tolist()}
    if control:
        res["control_gap"] = widest_ctrl
    return res
