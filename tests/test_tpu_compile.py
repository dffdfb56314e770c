"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

The TPU compiler ships with jaxlib and compiles for a chip that is
described, not attached, so these tests hold every kernel to Mosaic's
rules (block tiling, VMEM budget) at real widths without a chip: the
interpret-mode numerics tests in test_kernels.py cannot see either.  Each
test asserts the compiled program calls the kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and the fixture skips where it
cannot be described.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.cost_model import TPU_V5E
from repro.kernels.autotune import autotune

QWEN3 = get_config("qwen3-1.7b")
MAMBA2 = get_config("mamba2-1.3b")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _tiles(seq: int):
    return autotune(TPU_V5E, head_dim=QWEN3.hd,
                    group=QWEN3.n_heads // QWEN3.n_kv_heads,
                    d_model=QWEN3.d_model, vocab=QWEN3.padded_vocab, seq=seq)


def _compile(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("seq,backward", [(4096, True), (32768, False)],
                         ids=["train_4k_fwd_bwd", "prefill_32k_fwd"])
def test_flash_compiles(one_chip, seq, backward):
    from repro.kernels.flash_attention.ops import flash
    t = _tiles(seq)
    H, K, D = QWEN3.n_heads, QWEN3.n_kv_heads, QWEN3.hd

    def fwd(q, k, v):
        return flash(q, k, v, True, t.block_q, t.block_k, False, False)

    fn = (jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(), (0, 1, 2))
          if backward else fwd)
    text = _compile(fn, _spec(one_chip, (1, seq, H, D)),
                    _spec(one_chip, (1, seq, K, D)),
                    _spec(one_chip, (1, seq, K, D)))
    assert "tpu_custom_call" in text


def test_fused_xent_compiles(one_chip):
    """The LM loss head (fwd + both cotangents) at vocab 151936."""
    from repro.models.lm import fused_xent
    T, E, Vp = 4096, QWEN3.d_model, QWEN3.padded_vocab
    t = _tiles(T)

    def loss(h, w, lab):
        mask = jnp.ones(lab.shape, jnp.float32)
        s_nll, s_zl, _ = fused_xent(
            h, w, lab, mask, vocab=QWEN3.vocab, block_t=t.xent_block_t,
            block_v=t.xent_block_v, z_loss_coef=1e-4, interpret=False)
        return s_nll + s_zl

    text = _compile(jax.grad(loss, (0, 1)), _spec(one_chip, (1, T, E)),
                    _spec(one_chip, (E, Vp)),
                    _spec(one_chip, (1, T), jnp.int32))
    assert "tpu_custom_call" in text


def test_ssd_compiles(one_chip):
    """The SSD scan at Mamba2-1.3B widths: 64 heads of 64, d_state 128."""
    from repro.kernels.ssd.ssd import ssd_scan_pallas
    S, P, N = 4096, MAMBA2.ssd_headdim, MAMBA2.ssd_state
    H = 2 * MAMBA2.d_model // P
    f32 = jnp.float32
    text = _compile(
        lambda x, dt, A, Bm, Cm: ssd_scan_pallas(x, dt, A, Bm, Cm,
                                                 chunk=MAMBA2.ssd_chunk),
        _spec(one_chip, (1, S, H, P)), _spec(one_chip, (1, S, H), f32),
        _spec(one_chip, (H,), f32), _spec(one_chip, (1, S, 1, N)),
        _spec(one_chip, (1, S, 1, N)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("page_size", [64, 256])
def test_paged_decode_compiles(one_chip, page_size):
    """One decode step of 8 slots over a 1024-row paged cache."""
    from repro.kernels.flash_attention import paged_decode
    B, H, K, D = 8, QWEN3.n_heads, QWEN3.n_kv_heads, QWEN3.hd
    max_pages = 1024 // page_size
    P = B * max_pages + 1
    i32 = jnp.int32
    text = _compile(paged_decode, _spec(one_chip, (B, H, D)),
                    _spec(one_chip, (P, K, page_size, D)),
                    _spec(one_chip, (P, K, page_size, D)),
                    _spec(one_chip, (B, max_pages), i32),
                    _spec(one_chip, (B,), i32))
    assert "tpu_custom_call" in text
