"""The dense decoder layer: GQA attention with optional QKV bias, RoPE,
RMSNorm and a SwiGLU MLP (Qwen2 report, arXiv:2407.10671; Dream-7B shares
the backbone), written from the published equations.

The family contract is in ``bench/families/__init__.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import HIGHEST, _mm, _rmsnorm, _rope

KEYS = ("d_ff",)


def shapes(m: dict) -> dict:
    """Per-layer leaf name -> (shape, kind)."""
    L, d, hd = m["n_layers"], m["d_model"], m["head_dim"]
    nq, nkv, ff = m["n_heads"], m["n_kv_heads"], m["d_ff"]
    out = {
        "norm1": ((L, d), "gain"), "norm2": ((L, d), "gain"),
        "wq": ((L, d, nq * hd), "mat"), "wk": ((L, d, nkv * hd), "mat"),
        "wv": ((L, d, nkv * hd), "mat"), "wo": ((L, nq * hd, d), "mat"),
        "wi_gate": ((L, d, ff), "mat"), "wi_up": ((L, d, ff), "mat"),
        "wo_mlp": ((L, ff, d), "mat"),
    }
    if m["qkv_bias"]:
        out.update(bq=((L, nq * hd), "bias"), bk=((L, nkv * hd), "bias"),
                   bv=((L, nkv * hd), "bias"))
    return out


def program_slot(w: dict) -> dict:
    """The layer's leaves as a slot of the serving program's params."""
    attn = {k: w[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
            if k in w}
    return {"norm1": {"w": w["norm1"]}, "norm2": {"w": w["norm2"]},
            "attn": attn,
            "mlp": {"wi_gate": w["wi_gate"], "wi_up": w["wi_up"],
                    "wo": w["wo_mlp"]}}


def layer(m, low, x, lw, past_k, past_v, past_ok, pos):
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


def layer_params(m: dict) -> int:
    """Matmul weights of one decoder layer (projections + gated FFN)."""
    d, hd, nq, nkv, ff = (m["d_model"], m["head_dim"], m["n_heads"],
                          m["n_kv_heads"], m["d_ff"])
    return d * nq * hd + 2 * d * nkv * hd + nq * hd * d + 3 * d * ff


def layer_flops(m: dict, tokens: int) -> int:
    """Matmul FLOPs of ``tokens`` tokens in one layer: every token meets
    every weight once."""
    return tokens * 2 * layer_params(m)
