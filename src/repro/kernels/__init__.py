"""Pallas TPU kernels for the compute hot-spots CDLM optimizes.

- ``block_attn``  — block-causal flash attention (training / prefill);
- ``decode_attn`` — flash-decode of a B-token active block vs the KV cache
                    (the §4.3 serving hot loop), GQA groups folded into
                    query rows for MXU utilization;
- ``xent``        — fused streaming large-vocab softmax cross-entropy
                    (150k–256k-vocab lm-head loss without (T, V) logits);
- ``select``      — fused unembed + online-softmax candidate selection
                    (the §4.3 decode loop's per-step confidence/argmax
                    without (b, L, V) logits).

Each subpackage: ``<name>.py`` (pl.pallas_call + BlockSpec), ``ops.py``
(jit'd model-layout wrapper), ``ref.py`` (pure-jnp oracle). Validated with
``interpret=True`` shape/dtype sweeps in tests/test_kernels.py /
tests/test_select_kernel.py, and compiled for a described TPU v5e at
qwen2-0.5b widths in tests/test_tpu_compile.py; every op resolves
``interpret=None`` through :func:`default_interpret`, so real accelerators
compile the kernels and CPU runs emulate them without call sites having to
care.
"""
import jax


def default_interpret() -> bool:
    """Backend-aware default for the ``interpret`` flag of every kernel op.

    Every kernel in this repo is TPU-flavored Pallas (``pltpu`` memory
    spaces, compiler params, scalar prefetch), so only a TPU backend can
    actually compile them — everywhere else (CPU tests/CI, GPU) they run
    under the interpreter. Resolved at trace time, so an op called with
    ``interpret=None`` does the right thing on whatever backend jax
    selected."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret) -> bool:
    """``None`` -> :func:`default_interpret`; explicit bools pass through."""
    return default_interpret() if interpret is None else bool(interpret)


def pallas_calls(jaxpr):
    """``(kernel name, interpreted?)`` for every ``pallas_call`` in a traced
    program (``jax.make_jaxpr`` output), sub-jaxprs included: which kernels
    the program runs, and whether any of them runs under the interpreter."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    calls = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            calls.append((eqn.params["jaxpr"].debug_info.func_name,
                          bool(eqn.params["interpret"])))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    calls.extend(pallas_calls(sub))
    return calls


from repro.kernels import block_attn, decode_attn, select, xent  # noqa: F401,E402
