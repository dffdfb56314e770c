"""Pallas TPU SSD (state-space duality) chunked-scan kernel.

Mamba2's SSD decomposes the linear recurrence into (i) an intra-chunk
*quadratic dual form* — dense (Q, Q) decay-masked attention that runs on the
MXU — and (ii) an inter-chunk state recurrence with O(state) carry.  GPU
implementations split this into 4-5 separate kernels + a host-level scan;
on TPU we fuse everything into ONE grid walk:

- Head-major layout: x ``(B, H, S, P)``, B/C ``(B, G, S, N)`` (a head reads
  its group's slab through the index_map — B/C are never repeated per
  head in HBM) and dt ``(B, H, 1, S)`` as a lane-dense row, so every block's
  last two dims are (rows, full width) as Mosaic requires.  A is read from
  SMEM by head index.
- Grid ``(B, H, L)`` with L (chunk index) as the *minor* sequential axis:
  TPU grid steps execute in order, so the running state h ∈ (P, N) lives in
  a VMEM scratch buffer across chunk steps — the inter-chunk recurrence
  costs zero HBM traffic (the GPU version round-trips states through HBM).
- Per program: load the chunk's (Q, P) x-tile and (Q, N) B/C tiles, build
  the (Q, Q) decay mask from the dt prefix sums, do the three MXU matmuls
  (CBᵀ∘L)·x, state read C·h, and state update Bᵀ·(decay∘x).  The prefix
  sums and the row↔column moves are masked (Q, Q) reductions on the VPU,
  not cumsum/transposes, which Mosaic does not lower for these shapes.

Validated in interpret mode against the sequential-scan oracle (ref.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, hout_ref, h_scr,
                *, chunk: int):
    """Program (b, h, l): one chunk of one head of one batch row.

    a_ref: (H,) SMEM   x_ref: (Q, P)   dt_ref: (1, Q)   b_ref/c_ref: (Q, N)
    y_ref: (Q, P)  hout_ref: (P, N)  h_scr: (P, N) VMEM carry.
    """
    li = pl.program_id(2)
    Q = chunk

    @pl.when(li == 0)
    def _init():
        h_scr[...] = jnp.zeros(h_scr.shape, jnp.float32)

    x = x_ref[...].astype(jnp.float32)              # (Q, P)
    dt_row = dt_ref[...].astype(jnp.float32)        # (1, Q)
    Bm = b_ref[...].astype(jnp.float32)             # (Q, N)
    Cm = c_ref[...].astype(jnp.float32)             # (Q, N)
    dA_row = dt_row * a_ref[pl.program_id(1)]       # (1, Q) ≤ 0

    iota = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jota = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    lower, diag = iota >= jota, iota == jota

    def to_col(row):                                # (1, Q) → (Q, 1)
        return jnp.sum(jnp.where(diag, row, 0.0), axis=1, keepdims=True)

    # prefix sums cum_i = Σ_{j≤i} dA_j, as a column and as a row
    cum = jnp.sum(jnp.where(lower, dA_row, 0.0), axis=1, keepdims=True)
    cum_row = jnp.sum(jnp.where(diag, cum, 0.0), axis=0, keepdims=True)
    total = jnp.sum(dA_row, axis=1, keepdims=True)  # (1, 1)
    # intra-chunk decay mask  L[i, j] = exp(cum_i − cum_j) · (i ≥ j)
    Lmask = jnp.where(lower, jnp.exp(cum - cum_row), 0.0)

    xd = x * to_col(dt_row)                         # dt-weighted input
    # --- dual quadratic form on the MXU ---
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (Q, Q)
    y_diag = jax.lax.dot_general(scores * Lmask, xd,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (Q, P)
    # --- carried-state contribution: y_off = (C · h) ∘ exp(cum) ---
    h = h_scr[...]                                  # (P, N)
    y_off = jax.lax.dot_general(Cm, h, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)   # (Q, P)
    y_ref[...] = (y_diag + y_off * jnp.exp(cum)).astype(y_ref.dtype)

    # --- state update: h' = exp(sum dA) · h + Σ_q exp(cum_Q − cum_q) Bq ⊗ xdq
    state_upd = jax.lax.dot_general(
        xd * jnp.exp(total - cum), Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)         # (P, N)
    h_new = h * jnp.exp(total) + state_upd
    h_scr[...] = h_new

    @pl.when(li == pl.num_programs(2) - 1)
    def _emit():
        hout_ref[...] = h_new


def ssd_scan_pallas(x: jax.Array, dt: jax.Array, A: jax.Array,
                    Bm: jax.Array, Cm: jax.Array, *, chunk: int = 128,
                    interpret: bool = False):
    """x: (B, S, H, P)  dt: (B, S, H)  A: (H,)  Bm/Cm: (B, S, G, N).

    → (y (B, S, H, P) f32, final state (B, H, P, N) f32).
    """
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    chunk = min(chunk, S)
    if S % chunk:
        raise ValueError(f"S={S} must divide chunk={chunk}")
    L = S // chunk
    rep = H // G

    xh = jnp.swapaxes(x, 1, 2)                          # (B, H, S, P)
    dth = jnp.swapaxes(dt, 1, 2)[:, :, None, :]         # (B, H, 1, S)
    Bh, Ch = jnp.swapaxes(Bm, 1, 2), jnp.swapaxes(Cm, 1, 2)   # (B, G, S, N)

    def own(b, h, l):
        return (b, h, l, 0)

    def group(b, h, l):
        return (b, h // rep, l, 0)

    y, hT = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=(Bsz, H, L),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, None, chunk, P), own),
            pl.BlockSpec((None, None, 1, chunk), lambda b, h, l: (b, h, 0, l)),
            pl.BlockSpec((None, None, chunk, N), group),
            pl.BlockSpec((None, None, chunk, N), group),
        ],
        out_specs=(
            pl.BlockSpec((None, None, chunk, P), own),
            pl.BlockSpec((None, None, P, N), lambda b, h, l: (b, h, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((Bsz, H, S, P), jnp.float32),
            jax.ShapeDtypeStruct((Bsz, H, P, N), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(A.astype(jnp.float32), xh, dth, Bh, Ch)
    return jnp.swapaxes(y, 1, 2), hT
