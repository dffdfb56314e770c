"""Pallas TPU fused vocab-tiled softmax cross-entropy.

The paper's Fig-4 hot spot: a 100k-way (here up to 256k-way) classifier
whose logits tensor dwarfs everything else.  The kernel never materialises
(T, V) logits in HBM — it streams vocab tiles through VMEM and maintains the
online max / sum-exp / label-logit reduction per token row:

- Grid ``(nt, nv)``: token-block × vocab-block, vocab as the *minor*
  (fastest-moving) axis so the (block_t, E) hidden tile stays resident in
  VMEM across the whole vocab sweep while weight tiles (E, block_v) stream
  through — one HBM pass over the head weights per token block.
- The partial state (m, l, correct) is carried in VMEM scratch across
  grid steps (TPU grids execute sequentially over the minor axis, the
  standard Pallas accumulation idiom) and finalised on the last vocab tile.
- Operands reach the MXU in their own dtype (bf16 in training) with f32
  accumulation; per-token labels and results are (T, 1) columns.
- The (block_t, block_v) logits tile is MXU-shaped ((128, 512) by default)
  and exists only in VMEM: HBM traffic drops from O(T·V) to O(T·E + E·V),
  which is what makes the 256k-vocab gemma/seamless heads trainable.
- Composes with the paper's operator-split: under a vocab-sharded head each
  shard runs the kernel on its V/tp slice and the (m, l, correct) triples
  are combined with three tiny all-reduces (see models/lm.chunked_xent).

Backward is analytic (softmax − onehot), recomputing logits tile-by-tile —
same memory profile (custom_vjp in ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _xent_kernel(h_ref, w_ref, lab_ref, nll_ref, lse_ref, m_sc, l_sc, c_sc,
                 *, block_t: int, block_v: int, vocab: int):
    """Program (ti, vi): logits tile = h_tile @ w_tile, online reduce.

    h_ref: (bt, E)  w_ref: (E, bv)  lab_ref/nll_ref/lse_ref: (bt, 1)
    scratch m/l/c: (bt, 1) f32 — running max, sum-exp, label logit.
    """
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        c_sc[...] = jnp.zeros(c_sc.shape, jnp.float32)

    logits = jax.lax.dot_general(h_ref[...], w_ref[...],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    col = vi * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_t, block_v), 1)
    logits = jnp.where(col < vocab, logits, NEG_INF)         # padded cols
    hit = col == lab_ref[...]                                # (bt, bv)

    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    m_sc[...] = m_new
    l_sc[...] = l_sc[...] * corr + jnp.exp(logits - m_new).sum(
        axis=-1, keepdims=True)
    c_sc[...] = c_sc[...] + jnp.sum(jnp.where(hit, logits, 0.0), axis=-1,
                                    keepdims=True)

    @pl.when(vi == pl.num_programs(1) - 1)
    def _finalize():
        lse = jnp.log(jnp.maximum(l_sc[...], 1e-30)) + m_sc[...]
        lse_ref[...] = lse
        nll_ref[...] = lse - c_sc[...]


def xent_fwd(hidden: jax.Array, head_w: jax.Array, labels: jax.Array, *,
             vocab: int | None = None, block_t: int = 128,
             block_v: int = 512, interpret: bool = False):
    """hidden: (T, E)  head_w: (E, V)  labels: (T,) → (nll, lse) each (T,)."""
    T, E = hidden.shape
    V = head_w.shape[1]
    vocab = vocab or V
    block_t = min(block_t, T)
    block_v = min(block_v, V)
    if T % block_t or V % block_v:
        raise ValueError(f"(T={T}, V={V}) must divide blocks "
                         f"({block_t}, {block_v})")
    nt, nv = T // block_t, V // block_v

    # per-token values travel as (T, 1) columns: a lane-padded 2-D block
    # is a layout Mosaic accepts, a rank-1 (block_t,) block is not
    row = pl.BlockSpec((block_t, 1), lambda t, v: (t, 0))
    col = jax.ShapeDtypeStruct((T, 1), jnp.float32)
    nll, lse = pl.pallas_call(
        functools.partial(_xent_kernel, block_t=block_t, block_v=block_v,
                          vocab=vocab),
        grid=(nt, nv),
        in_specs=[
            pl.BlockSpec((block_t, E), lambda t, v: (t, 0)),
            pl.BlockSpec((E, block_v), lambda t, v: (0, v)),
            row,
        ],
        out_specs=(row, row),
        out_shape=(col, col),
        scratch_shapes=[pltpu.VMEM((block_t, 1), jnp.float32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(hidden, head_w, labels.astype(jnp.int32)[:, None])
    return nll[:, 0], lse[:, 0]
