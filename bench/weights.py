"""Seeded random weights, made on the device in one jitted call.

The benchmark makes the weights itself, in the layout the serving program
consumes (``params["embed"|"final_norm"|"slots"]``, layers stacked on axis
0 of each leaf) and in the dtype they are served in. The plain reference
reads the same arrays: they come from the benchmark, not the program.

Scales: projections N(0, 1/fan_in); token embedding N(0, 0.02^2);
QKV biases N(0, 0.1^2) and norm gains 1 + N(0, 0.1^2), so the bias and
gain paths carry non-trivial values.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def root_key(seed: int):
    """A PRNG key for any whole ``seed`` (Python ints past 32 bits are
    folded in, not truncated)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def shapes(m: dict) -> dict:
    """Leaf name -> (shape, kind) for the model dict ``m``."""
    L, d, hd = m["n_layers"], m["d_model"], m["head_dim"]
    nq, nkv, ff, V = m["n_heads"], m["n_kv_heads"], m["d_ff"], m["vocab_size"]
    out = {
        "tok": ((V, d), "embed"),
        "final_norm": ((d,), "gain"),
        "norm1": ((L, d), "gain"), "norm2": ((L, d), "gain"),
        "wq": ((L, d, nq * hd), "mat"), "wk": ((L, d, nkv * hd), "mat"),
        "wv": ((L, d, nkv * hd), "mat"), "wo": ((L, nq * hd, d), "mat"),
        "wi_gate": ((L, d, ff), "mat"), "wi_up": ((L, d, ff), "mat"),
        "wo_mlp": ((L, ff, d), "mat"),
    }
    if m["qkv_bias"]:
        out.update(bq=((L, nq * hd), "bias"), bk=((L, nkv * hd), "bias"),
                   bv=((L, nkv * hd), "bias"))
    if not m["tie_embeddings"]:
        out["head"] = ((d, V), "mat")
    return out


def _leaf(key, shape, kind, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "mat":
        z = z * shape[-2] ** -0.5
    elif kind == "embed":
        z = z * 0.02
    elif kind == "bias":
        z = z * 0.1
    else:  # gain
        z = 1.0 + 0.1 * z
    return z.astype(dtype)


def flat_weights(m: dict, key, dtype=jnp.bfloat16) -> dict:
    """Every leaf by name; leaf ``i`` (sorted names) draws from
    ``fold_in(key, i)``."""
    spec = shapes(m)
    return {name: _leaf(jax.random.fold_in(key, i), *spec[name], dtype)
            for i, name in enumerate(sorted(spec))}


def to_program_layout(w: dict) -> dict:
    """The flat leaves arranged as the serving program's params tree."""
    attn = {k: w[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
            if k in w}
    slot = {"norm1": {"w": w["norm1"]}, "norm2": {"w": w["norm2"]},
            "attn": attn,
            "mlp": {"wi_gate": w["wi_gate"], "wi_up": w["wi_up"],
                    "wo": w["wo_mlp"]}}
    embed = {"tok": w["tok"]}
    if "head" in w:
        embed["head"] = w["head"]
    return {"embed": embed, "final_norm": {"w": w["final_norm"]},
            "slots": (slot,)}


def make_weights(m: dict, seed: int) -> dict:
    """Flat bf16 weights for model dict ``m``, made on the default device
    by one jitted call."""
    # the key is an argument, not a constant: one program serves all seeds
    return jax.jit(lambda k: flat_weights(m, k))(root_key(seed))
