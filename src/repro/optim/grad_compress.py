"""Int8 error-feedback gradient compression for cross-pod (DCN) all-reduce.

The multi-pod default strategy only sends *gradients* across the slow pod
axis.  Quantizing them to int8 with per-tensor scales cuts DCN bytes 4×;
1-bit-style error feedback (the residual of quantisation is carried to the
next step and re-added) keeps SGD convergence unaffected to first order
(Seide et al., 2014; Karimireddy et al., 2019).

Used inside a ``shard_map`` that is manual over the ``pod`` axis (see
``planner.jit_train_step(compress_pod=True)``): the psum operates on int32
(the sum of ≤256 int8 shards fits easily), then dequantises with the summed
scales.  The Pallas ``quant`` kernel is the fused on-chip encode; this module
is the jnp reference used under GSPMD (bit-identical semantics).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def quantize_int8(x: jax.Array, err: jax.Array | None = None):
    """x (+ carried error) → (int8 q, f32 scale, new error).

    Symmetric per-tensor scaling: q = round(x / s), s = max|x| / 127.
    """
    xf = x.astype(jnp.float32)
    if err is not None:
        xf = xf + err
    scale = jnp.max(jnp.abs(xf)) / 127.0
    scale = jnp.maximum(scale, 1e-30)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    new_err = xf - q.astype(jnp.float32) * scale
    return q, scale, new_err


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compressed_psum(x: jax.Array, axis: str, err: jax.Array | None = None,
                    *, mean: bool = True):
    """Error-feedback int8 psum over a manual shard_map axis.

    Every shard quantises with its own scale; the int32 sums of (q · 127)
    normalised values are combined with the max scale so the dequantised sum
    is exact up to int8 resolution.  Returns (reduced f32, new error).
    """
    q, scale, new_err = quantize_int8(x, err)
    # common scale: use the max over shards so all quanta are comparable —
    # requantise against it (error feedback absorbs the difference)
    smax = jax.lax.pmax(scale, axis)
    q2 = jnp.clip(jnp.round(dequantize_int8(q, scale) / smax),
                  -127, 127).astype(jnp.int8)
    # residual from requantisation also goes to the error carry
    new_err = new_err + dequantize_int8(q, scale) - dequantize_int8(q2, smax)
    total = jax.lax.psum(q2.astype(jnp.int32), axis)
    out = total.astype(jnp.float32) * smax
    if mean:
        out = out / jax.lax.axis_size(axis)
    return out.astype(x.dtype), new_err.astype(jnp.float32)


def init_error_tree(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def compressed_psum_tree(grads, axis: str, err_tree, *, mean: bool = True):
    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = treedef.flatten_up_to(err_tree)
    outs = [compressed_psum(g, axis, e, mean=mean)
            for g, e in zip(flat_g, flat_e)]
    new_g = treedef.unflatten([o[0] for o in outs])
    new_e = treedef.unflatten([o[1] for o in outs])
    return new_g, new_e
