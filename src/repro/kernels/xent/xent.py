"""Fused large-vocab softmax cross-entropy — Pallas TPU kernel.

The lm-head loss of the 150k–256k-vocab archs is the single largest
activation in training: materializing (T, V) logits at T = batch×seq is
O(GB). This kernel streams vocab tiles of the head matrix through VMEM,
maintaining the online logsumexp and the target logit in scratch, and never
materializes logits in HBM. The per-token loss is ``logsumexp - logit[y]``.

Grid: (T_tiles, V_tiles), V innermost ("arbitrary"). Each step computes the
(block_t × block_v) logit tile with one MXU matmul from the resident
(block_t × d) hidden tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _xent_kernel(h_ref, w_ref, y_ref, loss_ref, m_scr, l_scr, t_scr, *,
                 block_t, block_v, n_v):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        t_scr[...] = jnp.zeros_like(t_scr)

    h = h_ref[...].astype(jnp.float32)                    # (block_t, d)
    w = w_ref[...].astype(jnp.float32)                    # (d, block_v)
    logits = jax.lax.dot_general(h, w, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    vpos = vi * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_t, block_v), 1)
    y = y_ref[...]                                        # (block_t, 1)
    t_scr[...] = t_scr[...] + jnp.sum(
        jnp.where(vpos == y, logits, 0.0), axis=-1, keepdims=True)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_new), 0.0)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(jnp.exp(logits - m_new),
                                              axis=-1, keepdims=True)
    m_scr[...] = m_new

    @pl.when(vi == n_v - 1)
    def _finalize():
        logz = m_scr[...] + jnp.log(jnp.maximum(l_scr[...], 1e-30))
        loss_ref[...] = logz - t_scr[...]


def xent_forward(hidden, w, targets, *, block_t: int = 128,
                 block_v: int = 512, interpret=None):
    """hidden: (T, d); w: (d, V); targets: (T,) int32 -> loss (T,) fp32.

    T must be a multiple of block_t, V of block_v (ops.py pads). Targets
    and losses travel as ``(T, 1)`` columns in ``(block_t, 1)`` blocks,
    which Mosaic tiles the way XLA lays them out (1-D blocks do not
    compile for the TPU at T > 128)."""
    T, d = hidden.shape
    V = w.shape[1]
    assert T % block_t == 0 and V % block_v == 0
    n_t, n_v = T // block_t, V // block_v

    kernel = functools.partial(_xent_kernel, block_t=block_t,
                               block_v=block_v, n_v=n_v)
    loss = pl.pallas_call(
        kernel,
        grid=(n_t, n_v),
        in_specs=[
            pl.BlockSpec((block_t, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, block_v), lambda i, j: (0, j)),
            pl.BlockSpec((block_t, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T, 1), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_t, 1), jnp.float32),
            pltpu.VMEM((block_t, 1), jnp.float32),
            pltpu.VMEM((block_t, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(hidden, w, targets.reshape(T, 1))
    return loss[:, 0]
