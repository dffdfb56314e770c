"""Pallas TPU flash attention: blocked online-softmax, causal GQA, fwd + bwd.

TPU-native design (DESIGN.md §8):

- Head-major layout: q ``(B, H, Sq, D)`` and k/v ``(B, K, Sk, D)``, so
  every block's last two dims are ``(rows, D)`` — a multiple of the
  (8, 128) vreg tile, which is what Mosaic requires of a block.
- Grid ``(B, K, nq, nk)`` with the kv-block axis innermost: one program
  per (batch, kv-head, q-block, kv-block).  The online-softmax carry
  (m, l, acc) lives in VMEM scratch across the kv axis and is flushed on
  its last step, so K/V stream through VMEM one ``(block_k, D)`` tile at a
  time and the kernel's footprint does not grow with the sequence (32k
  prefill fits as easily as 4k).
- GQA: each program holds the G query heads that share one kv head (a
  ``(G, block_q, D)`` q block), so a K/V tile is fetched once per group.
- Causality: kv blocks entirely above the diagonal skip their compute
  (``pl.when``), and their index_map clamps to the last live block so the
  pipeline issues no DMA for them either — the ~2× causal FLOP saving.

Backward (training path): the standard recompute-style flash backward.
The forward additionally emits the per-row log-sum-exp; each backward
kernel *recomputes* the score tile from (q, k, lse) in VMEM:

- ``_flash_bwd_dq_kernel``: grid (B, K, nq, nk), same wedge as the
  forward.  p = exp(s − lse), dp = do·vᵀ, ds = p·(dp − δ), dq += τ·ds·k.
- ``_flash_bwd_dkv_kernel``: grid (B, K, nk, nq), q blocks innermost
  (causal ⇒ the ones before ⌊j·bk/bq⌋ are skipped), accumulating
  dv += pᵀ·do and dk += τ·dsᵀ·q in VMEM and writing each tile once.

δ (= rowsum(do∘o)) is a cheap elementwise reduction computed by the
wrapper; the custom VJP that saves/recomputes residuals lives in ops.py.
MXU operands stay in the input dtype with f32 accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

_NT = (((1,), (1,)), ((), ()))      # a · bᵀ
_NN = (((1,), (0,)), ((), ()))      # a · b
_TN = (((0,), (0,)), ((), ()))      # aᵀ · b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _causal_mask(s, qi, kj, block_q, block_k):
    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(qpos >= kpos, s, NEG_INF)


def _live(qi, kj, block_q, block_k):
    """Does any row of q block ``qi`` attend any column of kv block ``kj``?"""
    return kj * block_k <= (qi + 1) * block_q - 1


def _when_live(causal, qi, kj, block_q, block_k):
    if causal:
        return pl.when(_live(qi, kj, block_q, block_k))
    return lambda fn: fn()


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc,
                      *, block_q: int, block_k: int, causal: bool,
                      group: int, scale: float):
    """Program (b, kv-head, q-block i, kv-block j).

    q_ref/o_ref: (G, block_q, D)   k_ref/v_ref: (block_k, D)
    lse_ref: (G, block_q, 1)       scratch m/l: (G, block_q, 1), acc: (G, block_q, D)
    """
    qi, kj = pl.program_id(2), pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    @_when_live(causal, qi, kj, block_q, block_k)
    def _step():
        k, v = k_ref[...], v_ref[...]
        for g in range(group):
            s = _dot(q_ref[g], k, _NT) * scale
            if causal:
                s = _causal_mask(s, qi, kj, block_q, block_k)
            m_prev = m_sc[g]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            m_sc[g] = m_new
            l_sc[g] = l_sc[g] * corr + p.sum(axis=-1, keepdims=True)
            acc_sc[g] = acc_sc[g] * corr + _dot(p.astype(v.dtype), v, _NN)

    @pl.when(kj == pl.num_programs(3) - 1)
    def _flush():
        for g in range(group):
            l = jnp.maximum(l_sc[g], 1e-30)
            o_ref[g] = (acc_sc[g] / l).astype(o_ref.dtype)
            lse_ref[g] = m_sc[g] + jnp.log(l)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dq_ref,
                         dq_sc, *, block_q: int, block_k: int, causal: bool,
                         group: int, scale: float):
    """dQ program (b, kv-head, q-block i, kv-block j): recompute, wedge.

    q_ref/do_ref/dq_ref: (G, block_q, D)   k_ref/v_ref: (block_k, D)
    lse_ref/d_ref: (G, block_q, 1)         dq_sc: (G, block_q, D) f32
    """
    qi, kj = pl.program_id(2), pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        dq_sc[...] = jnp.zeros(dq_sc.shape, jnp.float32)

    @_when_live(causal, qi, kj, block_q, block_k)
    def _step():
        k, v = k_ref[...], v_ref[...]
        for g in range(group):
            s = _dot(q_ref[g], k, _NT) * scale
            if causal:
                s = _causal_mask(s, qi, kj, block_q, block_k)
            p = jnp.exp(s - lse_ref[g])              # masked → exp(−∞) = 0
            dp = _dot(do_ref[g], v, _NT)
            ds = p * (dp - d_ref[g])
            dq_sc[g] = dq_sc[g] + _dot(ds.astype(k.dtype), k, _NN)

    @pl.when(kj == pl.num_programs(3) - 1)
    def _flush():
        for g in range(group):
            dq_ref[g] = (dq_sc[g] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref,
                          dk_ref, dv_ref, dk_sc, dv_sc, *, block_q: int,
                          block_k: int, causal: bool, group: int,
                          scale: float):
    """dK/dV program (b, kv-head, kv-block j, q-block i): sum over the
    group's query heads and the live q blocks.

    q_ref/do_ref: (G, block_q, D)   k_ref/v_ref/dk_ref/dv_ref: (block_k, D)
    lse_ref/d_ref: (G, block_q, 1)  dk_sc/dv_sc: (block_k, D) f32
    """
    kj, qi = pl.program_id(2), pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_sc[...] = jnp.zeros(dk_sc.shape, jnp.float32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, jnp.float32)

    @_when_live(causal, qi, kj, block_q, block_k)
    def _step():
        k, v = k_ref[...], v_ref[...]
        for g in range(group):
            q, do = q_ref[g], do_ref[g]
            s = _dot(q, k, _NT) * scale
            if causal:
                s = _causal_mask(s, qi, kj, block_q, block_k)
            p = jnp.exp(s - lse_ref[g])
            dv_sc[...] = dv_sc[...] + _dot(p.astype(do.dtype), do, _TN)
            dp = _dot(do, v, _NT)
            ds = p * (dp - d_ref[g])
            dk_sc[...] = dk_sc[...] + _dot(ds.astype(q.dtype), q, _TN)

    @pl.when(qi == pl.num_programs(3) - 1)
    def _flush():
        dk_ref[...] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def _check_blocks(Sq: int, Sk: int, block_q: int, block_k: int):
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    if Sq % block_q or Sk % block_k:
        raise ValueError(f"seq ({Sq},{Sk}) must divide blocks "
                         f"({block_q},{block_k})")
    return block_q, block_k


def _params(n_parallel: int):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * n_parallel + ("arbitrary",))


def _q_major_specs(G: int, block_q: int, block_k: int, D: int, causal: bool):
    """BlockSpecs of a (B, K, nq, nk) grid: q tile, lse/δ rows, kv tile."""
    def kv_block(b, h, i, j):
        if causal:      # dead blocks re-address the last live one: no DMA
            j = jnp.minimum(j, ((i + 1) * block_q - 1) // block_k)
        return (b, h, j, 0)

    return (pl.BlockSpec((None, G, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, G, block_q, 1), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, block_k, D), kv_block))


def _to_head_major(q, k, v):
    """(B, S, H, D) → (B, H, S, D) for q and k/v."""
    return (jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2))


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False,
                    return_lse: bool = False):
    """q: (B, Sq, H, D)  k/v: (B, Sk, K, D) → (B, Sq, H, D).

    ``return_lse``: additionally return the per-row log-sum-exp
    (B, H, Sq) f32 — the residual the fused backward needs.  Training code
    should go through :func:`repro.kernels.flash_attention.ops.flash`,
    whose custom VJP runs the fused backward kernels.
    """
    B, Sq, H, D = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    block_q, block_k = _check_blocks(Sq, Sk, block_q, block_k)
    nq, nk = Sq // block_q, Sk // block_k
    qh, kh, vh = _to_head_major(q, k, v)
    q_spec, row_spec, kv_spec = _q_major_specs(G, block_q, block_k, D, causal)
    out, lse = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, group=G, scale=D ** -0.5),
        grid=(B, K, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=(q_spec, row_spec),
        out_shape=(jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
                   jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((G, block_q, 1), jnp.float32),
                        pltpu.VMEM((G, block_q, 1), jnp.float32),
                        pltpu.VMEM((G, block_q, D), jnp.float32)],
        compiler_params=_params(3),
        interpret=interpret,
    )(qh, kh, vh)
    out = jnp.swapaxes(out, 1, 2)
    return (out, lse[..., 0]) if return_lse else out


def flash_attention_bwd(q: jax.Array, k: jax.Array, v: jax.Array,
                        do: jax.Array, lse: jax.Array, delta: jax.Array, *,
                        causal: bool = True, block_q: int = 128,
                        block_k: int = 128, interpret: bool = False):
    """Fused flash backward: (dq, dk, dv) from saved (q, k, v, lse, δ).

    q/do: (B, Sq, H, D)  k/v: (B, Sk, K, D)  lse/delta: (B, H, Sq).
    Score tiles are recomputed in VMEM — no (Sq, Sk) tensor ever exists.
    """
    B, Sq, H, D = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    block_q, block_k = _check_blocks(Sq, Sk, block_q, block_k)
    nq, nk = Sq // block_q, Sk // block_k
    qh, kh, vh = _to_head_major(q, k, v)
    doh = jnp.swapaxes(do, 1, 2)
    lse, delta = lse[..., None], delta[..., None]
    common = dict(block_q=block_q, block_k=block_k, causal=causal, group=G,
                  scale=D ** -0.5)

    # --- dq: grid (B, K, nq, nk), kv innermost ---
    q_tile, row_tile, kv_tile = _q_major_specs(G, block_q, block_k, D, causal)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **common),
        grid=(B, K, nq, nk),
        in_specs=[q_tile, kv_tile, kv_tile, q_tile, row_tile, row_tile],
        out_specs=q_tile,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((G, block_q, D), jnp.float32)],
        compiler_params=_params(3),
        interpret=interpret,
    )(qh, kh, vh, doh, lse, delta)

    # --- dk/dv: grid (B, K, nk, nq), q innermost ---
    def q_block(b, h, j, i):
        if causal:      # q blocks before the diagonal re-address the first live one
            i = jnp.maximum(i, (j * block_k) // block_q)
        return (b, h, i, 0)

    q_rows = pl.BlockSpec((None, G, block_q, D), q_block)
    lse_rows = pl.BlockSpec((None, G, block_q, 1), q_block)
    kv_own = pl.BlockSpec((None, None, block_k, D),
                          lambda b, h, j, i: (b, h, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **common),
        grid=(B, K, nk, nq),
        in_specs=[q_rows, kv_own, kv_own, q_rows, lse_rows, lse_rows],
        out_specs=(kv_own, kv_own),
        out_shape=(jax.ShapeDtypeStruct((B, K, Sk, D), k.dtype),
                   jax.ShapeDtypeStruct((B, K, Sk, D), v.dtype)),
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        compiler_params=_params(3),
        interpret=interpret,
    )(qh, kh, vh, doh, lse, delta)
    return (jnp.swapaxes(dq, 1, 2), jnp.swapaxes(dk, 1, 2),
            jnp.swapaxes(dv, 1, 2))
