"""Plain float32 building blocks shared by the benchmark's references.

Nothing here imports the program.  Every contraction runs at
``precision=HIGHEST`` (a TPU otherwise multiplies float32 in one bf16
pass).  ``mode="fp8"`` is the control: each contraction's operands are
first rounded to float8 e4m3 with a per-tensor scale, the precision a
later change might be tempted to train in.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0          # largest finite float8_e4m3fn


def fp8_round(x: jax.Array) -> jax.Array:
    """x rounded to float8 e4m3 under a per-tensor scale, and back to
    float32.  The rounding passes gradients straight through, so the
    backward pass multiplies by the rounded operands as fp8 training
    would, and no gradient flows through the scale."""
    x = x.astype(jnp.float32)
    scale = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def mm(eq: str, a: jax.Array, b: jax.Array, mode: str = "f32") -> jax.Array:
    """einsum in float32 at full precision, or through fp8 operands."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if mode == "fp8":
        a, b = fp8_round(a), fp8_round(b)
    elif mode != "f32":
        raise ValueError(f"unknown reference mode {mode!r}")
    return jnp.einsum(eq, a, b, precision=HI)


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotate-half RoPE.  x: (B, S, H, D); positions: (S,)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]   # (S, d/2)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def token_loss(hidden: jax.Array, table: jax.Array, labels: jax.Array,
               vocab: int, z_coef: float, mode: str, chunk: int = 512):
    """Mean next-token cross-entropy plus ``z_coef`` · mean(logsumexp²).

    hidden: (B, T, E) final-normed states; table: (Vp, E) tied embedding
    (rows past ``vocab`` are padding and never predicted); labels: (B, T).
    The sequence is walked in chunks so only (B, chunk, Vp) logits live.
    """
    B, T, E = hidden.shape
    n = -(-T // chunk)
    pad = n * chunk - T
    h = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
    lab = jnp.pad(labels, ((0, 0), (0, pad)))
    live = jnp.pad(jnp.ones((B, T), jnp.float32), ((0, 0), (0, pad)))
    hs = jnp.moveaxis(h.reshape(B, n, chunk, E), 1, 0)
    ls = jnp.moveaxis(lab.reshape(B, n, chunk), 1, 0)
    ms = jnp.moveaxis(live.reshape(B, n, chunk), 1, 0)
    cols = jnp.arange(table.shape[0])

    @jax.checkpoint
    def body(acc, inp):
        hc, lc, mc = inp
        logits = mm("bce,ve->bcv", hc, table, mode)
        logits = jnp.where(cols < vocab, logits, -jnp.inf)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, lc[..., None], -1)[..., 0]
        return (acc[0] + jnp.sum((lse - picked) * mc),
                acc[1] + jnp.sum(lse * lse * mc)), None

    (nll, zz), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())),
                                (hs, ls, ms))
    count = B * T
    return nll / count + z_coef * zz / count
