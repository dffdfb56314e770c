"""Differentiable public op for the Pallas flash-attention kernels.

``pallas_call`` has no autodiff rule, so :func:`flash` carries an explicit
``jax.custom_vjp`` that routes the backward through the fused recompute
kernels in ``flash.py``.  Residual policy follows the stack-level
``attn_bwd_remat`` flag:

- ``bwd_remat=True`` (memory-lean, the flash paper's default): save only
  (q, k, v, lse) — O(S) extra — and *re-run the forward kernel* in the
  backward to rebuild ``o`` for the δ = rowsum(do∘o) reduction.
- ``bwd_remat=False``: additionally save ``o`` (O(S·D)) and skip the
  forward recompute — one fewer kernel launch at higher residency, the
  same trade ``models/attention.py`` exposes for the ref path.

Either way no (Sq, Sk) score matrix is ever materialised: both backward
kernels rebuild score tiles in VMEM from (q, k, lse).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash import (flash_attention,
                                                 flash_attention_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash(q, k, v, causal: bool = True, block_q: int = 128,
          block_k: int = 128, interpret: bool = False,
          bwd_remat: bool = True):
    """q: (B, Sq, H, D), k/v: (B, Sk, K, D) → (B, Sq, H, D).

    Differentiable: fwd and bwd both run fused Pallas kernels.
    """
    return flash_attention(q, k, v, causal=causal, block_q=block_q,
                           block_k=block_k, interpret=interpret)


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret, bwd_remat):
    out, lse = flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interpret,
                               return_lse=True)
    res = (q, k, v, lse) if bwd_remat else (q, k, v, lse, out)
    return out, res


def _flash_bwd(causal, block_q, block_k, interpret, bwd_remat, res, do):
    if bwd_remat:
        q, k, v, lse = res
        out = flash_attention(q, k, v, causal=causal, block_q=block_q,
                              block_k=block_k, interpret=interpret)
    else:
        q, k, v, lse, out = res
    # δ_i = Σ_d do_i·o_i — cheap elementwise reduce, laid out like lse
    delta = jnp.swapaxes(jnp.sum(do.astype(jnp.float32)
                                 * out.astype(jnp.float32), axis=-1), 1, 2)
    dq, dk, dv = flash_attention_bwd(q, k, v, do, lse, delta, causal=causal,
                                     block_q=block_q, block_k=block_k,
                                     interpret=interpret)
    return dq, dk, dv


flash.defvjp(_flash_fwd, _flash_bwd)
