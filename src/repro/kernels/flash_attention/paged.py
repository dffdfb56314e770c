"""Pallas paged flash-decode: block-table-indexed attention over page pools.

The serving tier's paged KV cache (DESIGN.md §9) stores KV in fixed-size
physical pages ``(P, page_size, K, D)``; each decode slot owns a row of a
``(B, max_pages)`` block table mapping logical page *j* to a physical page.
This kernel computes one decode step's attention reading KV **through the
block table** — the gap pages a dense cache would stream (slots reserve
``max_len`` but hold ``pos`` tokens) are never touched.

TPU-native shape, following ``flash.py``:

- Head-major pools ``(P, K, page_size, D)``: one page of one kv head is a
  ``(page_size, D)`` block, and q/out travel as ``(B, K, G, D)`` so their
  blocks are ``(G, D)`` tiles — every block's last two dims are (rows,
  full width), the layout Mosaic accepts.
- Grid ``(B, K, max_pages)`` with the page index innermost.  The page loop
  must be a *grid* dimension (not an in-kernel ``fori_loop``) because the
  physical page address is data-dependent: the k/v BlockSpec index_map
  reads the scalar-prefetched block table — ``(bt[b, j], k, 0, 0)`` — and
  the Pallas pipeline DMAs exactly that page into VMEM.  That indirection
  is the whole trick; everything else is flash-decode.
- ``pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=2)``: the block table
  and positions arrive in SMEM before the body runs, so index_maps can use
  them.
- The online-softmax carry (m, l, acc) lives in VMEM scratch, initialised
  at ``j == 0`` and flushed to the output at ``j == max_pages − 1``.
- Pages past ``pos[b]`` skip their compute, and their index_map re-addresses
  the last live page so the pipeline issues no DMA for them.  Positions
  ≥ ``pos[b]`` inside the last live page mask to NEG_INF.

Validated in interpret mode on CPU against the gather-based ref path
(``models.attention.paged_decode_attention(impl="ref")``); on TPU the same
code lowers to Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_decode_kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                         m_sc, l_sc, acc_sc, *, page_size: int, scale: float):
    """One (batch-slot, kv-head, logical-page) program.

    bt_ref: (B, max_pages) SMEM   pos_ref: (B,) SMEM
    q_ref/o_ref: (G, D) VMEM      k_ref/v_ref: (page_size, D) VMEM (the
    physical page the index_map resolved)
    m_sc/l_sc: (G, 1) f32 scratch   acc_sc: (G, D) f32 scratch
    """
    b, j = pl.program_id(0), pl.program_id(2)
    pos = pos_ref[b]

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    @pl.when(j * page_size <= pos)
    def _step():
        k, v = k_ref[...], v_ref[...]
        s = jax.lax.dot_general(q_ref[...], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos <= pos, s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_sc[...] = m_new
        l_sc[...] = l_sc[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)
                      ).astype(o_ref.dtype)


def paged_decode(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                 block_table: jax.Array, pos: jax.Array, *,
                 interpret: bool = False) -> jax.Array:
    """q: (B, H, D); k_pool/v_pool: (P, K, page_size, D);
    block_table: (B, max_pages) int32; pos: (B,) int32 → (B, H, D).

    The new token's KV must already be written into the pools (the caller
    scatters first, then attends — ``kpos <= pos`` includes the new cell).
    """
    B, H, D = q.shape
    _, K, ps, _ = k_pool.shape
    G = H // K
    max_pages = block_table.shape[1]

    def page(b, h, j, bt, pos):      # dead pages re-address the last live one
        return (bt[b, jnp.minimum(j, pos[b] // ps)], h, 0, 0)

    q_spec = pl.BlockSpec((None, None, G, D), lambda b, h, j, bt, pos: (b, h, 0, 0))
    kv_spec = pl.BlockSpec((None, None, ps, D), page)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, max_pages),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, page_size=ps,
                          scale=D ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_table, pos, q.reshape(B, K, G, D), k_pool, v_pool)
    return out.reshape(B, H, D)
