"""Public wrapper for the fused xent kernel with an analytic custom VJP.

Forward: the Pallas kernel (never materialises (T, V) logits).
Backward: d_logits = softmax − onehot(label); dh = d_logits @ Wᵀ and
dW = hᵀ @ d_logits are computed *chunk by chunk over the vocab* with the
saved (lse) — logits are recomputed per chunk, so the backward has the same
O(T·E + E·V) HBM profile as the forward (flash-style recompute-in-backward,
here in plain jnp over vocab chunks since the contraction itself is a plain
matmul XLA already runs at roofline).

The backward's chunk width is its own, derived from the shapes by
:func:`bwd_chunk`, not the forward kernel's VMEM-bound ``block_v``: the dh
product contracts over the chunk, so every chunk reads and writes the whole
(T, E) f32 accumulator, and a chunk narrower than E makes that traffic, and
the loop's per-chunk overhead, outweigh the multiply-adds.  A chunk of
1.5E to 3E columns gives dh deeper pieces than the logits product's (E);
on a v5e at E = 2048 the loss head ran fastest there (3456 and 4224
columns), against 2304 and 4608 and the forward's 256.  The chunk lives in
HBM, so only a byte ceiling on its (T, chunk) f32 tile bounds it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.xent.xent import xent_fwd

# ceiling on one (T, chunk) f32 tile of the backward, so a long-sequence
# step does not trade its activations for a wide chunk
_CHUNK_TILE_BYTES = 256 << 20
_LANE = 128


def bwd_chunk(T: int, E: int, V: int) -> tuple[int, int, int]:
    """The backward's vocab chunk for T rows, width E and V (padded) columns:
    ``(chunk, n_full, tail)`` with ``chunk * n_full + tail == V``.

    The chunk is the smallest lane-multiple divisor of V in [3E/2, 3E];
    where V has none, 3E/2 rounded up to a lane multiple, the rest of V one
    static tail chunk.  Either way it is capped so a (T, chunk) f32 tile
    stays within ``_CHUNK_TILE_BYTES``, and never wider than V.
    """
    cap = max(_LANE, _CHUNK_TILE_BYTES // (4 * T) // _LANE * _LANE)
    lo = min(-(-(3 * E // 2) // _LANE) * _LANE, cap)
    for c in range(lo, min(3 * E, cap) + 1, _LANE):
        if V % c == 0:
            return c, V // c, 0
    chunk = min(lo, V)
    return chunk, V // chunk, V % chunk


def _vocab_sweep(hidden, head_w, labels, lse, vocab, d_logits):
    """dh, dW of the loss given each chunk's ``d_logits(p, onehot)``, with
    p the softmax (zero on padded columns) and onehot the labels' columns:
    the logits recomputed chunk by chunk in f32, ``bwd_chunk`` wide."""
    T, E = hidden.shape
    V = head_w.shape[1]
    vocab_ = vocab or V
    chunk, n_full, tail = bwd_chunk(T, E, V)
    hf = hidden.astype(jnp.float32)

    def grads(start, width, dh):
        w_t = jax.lax.dynamic_slice(head_w, (0, start), (E, width)) \
            .astype(jnp.float32)
        logits = hf @ w_t
        col = jnp.arange(width)[None, :] + start
        p = jnp.where(col < vocab_,
                      jnp.exp(logits - lse[:, None]), 0.0)       # softmax
        onehot = jnp.where(col == labels[:, None], 1.0, 0.0)
        d = d_logits(p, onehot)
        return dh + d @ w_t.T, hf.T @ d

    def body(i, carry):
        dh, dw = carry
        dh, dw_c = grads(i * chunk, chunk, dh)
        return dh, jax.lax.dynamic_update_slice(dw, dw_c, (0, i * chunk))

    dh0 = jnp.zeros((T, E), jnp.float32)
    dw0 = jnp.zeros((E, V), jnp.float32)
    dh, dw = jax.lax.fori_loop(0, n_full, body, (dh0, dw0))
    if tail:
        start = n_full * chunk
        dh, dw_c = grads(start, tail, dh)
        dw = jax.lax.dynamic_update_slice(dw, dw_c, (0, start))
    return dh.astype(hidden.dtype), dw.astype(head_w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def xent(hidden, head_w, labels, vocab=None, block_t=128, block_v=512,
         interpret=False):
    """hidden (T, E), head_w (E, V), labels (T,) → nll (T,) fp32."""
    nll, _ = xent_fwd(hidden, head_w, labels, vocab=vocab, block_t=block_t,
                      block_v=block_v, interpret=interpret)
    return nll


def _fwd(hidden, head_w, labels, vocab, block_t, block_v, interpret):
    nll, lse = xent_fwd(hidden, head_w, labels, vocab=vocab, block_t=block_t,
                        block_v=block_v, interpret=interpret)
    return nll, (hidden, head_w, labels, lse)


def _bwd(vocab, block_t, block_v, interpret, res, g):
    hidden, head_w, labels, lse = res
    dh, dw = _vocab_sweep(hidden, head_w, labels, lse, vocab,
                          lambda p, onehot: (p - onehot) * g[:, None])
    return dh, dw, None


xent.defvjp(_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def xent_with_lse(hidden, head_w, labels, vocab=None, block_t=128,
                  block_v=512, interpret=False):
    """Like :func:`xent` but also returns lse (T,) — differentiably.

    The LM loss needs lse for the z-loss term (z = lse² regulariser), so
    both outputs carry cotangents.  With g = (g_nll, g_lse):

        d_logits = g_nll·(softmax − onehot) + g_lse·softmax

    computed with the same recompute-over-vocab-chunks sweep as :func:`xent`.
    """
    return xent_fwd(hidden, head_w, labels, vocab=vocab, block_t=block_t,
                    block_v=block_v, interpret=interpret)


def _fwd_lse(hidden, head_w, labels, vocab, block_t, block_v, interpret):
    nll, lse = xent_fwd(hidden, head_w, labels, vocab=vocab, block_t=block_t,
                        block_v=block_v, interpret=interpret)
    return (nll, lse), (hidden, head_w, labels, lse)


def _bwd_lse(vocab, block_t, block_v, interpret, res, g):
    hidden, head_w, labels, lse = res
    g_nll = g[0].astype(jnp.float32)[:, None]
    g_lse = g[1].astype(jnp.float32)[:, None]
    dh, dw = _vocab_sweep(
        hidden, head_w, labels, lse, vocab,
        lambda p, onehot: g_nll * (p - onehot) + g_lse * p)
    return dh, dw, None


xent_with_lse.defvjp(_fwd_lse, _bwd_lse)
