"""Compile-only checks of the main-path Pallas kernels for a TPU v5e that is
described, not attached: the chip's own compiler (Mosaic) must accept each
kernel at qwen2-0.5b widths in bfloat16 (the paged kernel at dream-7b
widths too), and the compiled program must
contain the kernel as a ``tpu_custom_call``. Interpret-mode tests cannot
see layout or tiling refusals; this file can, without a chip.

The topology is described inside a module fixture, never at import: only
one process may load the TPU compiler library, and under several test
workers only the worker that runs this file should."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels.decode_attn.decode_attn import (
    decode_attention_partial,
    paged_decode_attention_partial,
)
from repro.kernels.select.select import select_forward
from repro.kernels.xent.xent import xent_forward

CFG = get_config("qwen2-0.5b")
BF16 = jnp.bfloat16
LANES, BLOCK, PROMPT, GEN = 8, 32, 512, 256
VP = -(-CFG.vocab_size // 512) * 512      # ops.py pads V to block_v


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    prev = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs in the tmpdir
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure: no topology
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        if prev is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def shape(topo):
    """``shape(dims, dtype)``: an argument placed on one described chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                    sharding=one_chip)


def _compile_text(fn, *shapes):
    """Compile ``fn`` for the described chip with the persistent cache off
    (a described-chip entry could not be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(fn).lower(*shapes).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("T", [LANES * BLOCK, 1024])
def test_select_compiles(shape, T):
    text = _compile_text(
        lambda h, w, m: select_forward(h, w, m, v_total=CFG.vocab_size,
                                       interpret=False),
        shape((T, CFG.d_model), BF16), shape((CFG.d_model, VP), BF16),
        shape((T,), jnp.int32))
    assert "tpu_custom_call" in text


def test_xent_compiles(shape):
    text = _compile_text(
        lambda h, w, y: xent_forward(h, w, y, interpret=False),
        shape((1024, CFG.d_model), BF16), shape((CFG.d_model, VP), BF16),
        shape((1024,), jnp.int32))
    assert "tpu_custom_call" in text


def test_decode_attention_compiles(shape):
    kv, g, hd = CFG.n_kv_heads, CFG.q_per_kv, CFG.head_dim
    S_len = PROMPT + GEN
    text = _compile_text(
        lambda q, k, v, n: decode_attention_partial(
            q, k, v, n, scale=hd ** -0.5, g=g, interpret=False),
        shape((LANES * kv, BLOCK * g, hd), BF16),
        shape((LANES * kv, S_len, hd), BF16), shape((LANES * kv, S_len, hd), BF16),
        shape((), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "dream-7b"])
def test_paged_decode_attention_compiles(shape, arch):
    """The lane-batched paged kernel as the serving engine calls it: 16
    lanes, 24-page tables of 32 slots, a 384-page pool. Its double-buffered
    chunk of pages must fit the kernel's VMEM at both head shapes."""
    cfg = get_config(arch)
    kv, g, hd = cfg.n_kv_heads, cfg.q_per_kv, cfg.head_dim
    lanes, n_t, n_pages = 16, (PROMPT + GEN) // BLOCK, 384
    text = _compile_text(
        lambda q, k, v, pt, n: paged_decode_attention_partial(
            q, k, v, pt, n, scale=hd ** -0.5, g=g, interpret=False),
        shape((lanes, kv, BLOCK * g, hd), BF16),
        shape((kv, n_pages, BLOCK, hd), BF16), shape((kv, n_pages, BLOCK, hd), BF16),
        shape((lanes, n_t), jnp.int32), shape((lanes,), jnp.int32))
    assert "tpu_custom_call" in text
