"""Seeded random weights, made on the device in one jitted call.

The benchmark makes the weights itself, in the layout the serving program
consumes (``params["embed"|"final_norm"|"slots"]``, layers stacked on axis
0 of each leaf) and in the dtype they are served in. The plain reference
reads the same arrays: they come from the benchmark, not the program.

Leaves are the model family's per-layer ones (``bench/families/``) and
the embedding, final norm and untied head. Scales by kind: ``mat``
N(0, 1/fan_in); ``embed`` N(0, 0.02^2); ``bias`` N(0, 0.1^2) and ``gain``
1 + N(0, 0.1^2), so the bias and gain paths carry non-trivial values. A
family with kinds of its own draws them itself.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import families

KINDS = ("mat", "embed", "bias", "gain")  # drawn here; a family draws others


def root_key(seed: int):
    """A PRNG key for any whole ``seed`` (Python ints past 32 bits are
    folded in, not truncated)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def shapes(m: dict) -> dict:
    """Leaf name -> (shape, kind) for the model dict ``m``: its family's
    per-layer leaves, the embedding, the final norm and an untied head."""
    d, V = m["d_model"], m["vocab_size"]
    out = dict(families.of(m).shapes(m), tok=((V, d), "embed"),
               final_norm=((d,), "gain"))
    if not m["tie_embeddings"]:
        out["head"] = ((d, V), "mat")
    return out


def _leaf(family, key, shape, kind, dtype):
    if kind not in KINDS:
        return family.leaf(key, shape, kind, dtype)
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
    spec, family = shapes(m), families.of(m)
    return {name: _leaf(family, jax.random.fold_in(key, i), *spec[name],
                        dtype)
            for i, name in enumerate(sorted(spec))}


def to_program_layout(w: dict, m: dict) -> dict:
    """The flat leaves arranged as the serving program's params tree."""
    embed = {"tok": w["tok"]}
    if "head" in w:
        embed["head"] = w["head"]
    return {"embed": embed, "final_norm": {"w": w["final_norm"]},
            "slots": (families.of(m).program_slot(w),)}


def make_weights(m: dict, seed: int) -> dict:
    """Flat bf16 weights for model dict ``m``, made on the default device
    by one jitted call."""
    # the key is an argument, not a constant: one program serves all seeds
    return jax.jit(lambda k: flat_weights(m, k))(root_key(seed))
