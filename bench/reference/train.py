"""The reference's training steps: float32 gradients of the reference loss
and a plain AdamW (global-norm clipping, bias correction), written from
the algorithm (Loshchilov & Hutter, arXiv:1711.05101) and not from the
program.  It returns the readings that the benchmark compares.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.weights import flatten


def _norms(tree) -> dict:
    return {p: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for p, v in flatten(tree).items()}


def adamw_update(g, m, v, p, t, opt):
    """One AdamW step on leaves (t counts from 1)."""
    b1, b2 = opt["b1"], opt["b2"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + opt["eps"])
    u = u + opt["weight_decay"] * p
    return p - opt["lr"] * u, m, v


def make_step(loss_fn, opt):
    """The jitted reference step (p, m, v, tokens, t) → (p, m, v, loss,
    clipped-gradient norms, raw-gradient norms).  The batch is walked one
    row at a time and the gradients averaged, which is the whole batch's
    gradient (the loss is a mean over equal-length rows)."""
    def one(p, m, v, tokens, t):
        n = tokens.shape[0]

        def acc(carry, row):
            loss, g = jax.value_and_grad(loss_fn)(p, row[None])
            return (carry[0] + loss,
                    jax.tree.map(jnp.add, carry[1], g)), None

        zero = (jnp.zeros(()), jax.tree.map(jnp.zeros_like, p))
        (loss, g), _ = jax.lax.scan(acc, zero, tokens)
        loss = loss / n
        g = jax.tree.map(lambda x: x / n, g)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
        clip = jnp.minimum(1.0, opt["max_grad_norm"] / jnp.maximum(gn, 1e-9))
        raw = _norms(g)
        g = jax.tree.map(lambda x: x * clip, g)
        out = jax.tree.map(lambda g_, m_, v_, p_: adamw_update(
            g_, m_, v_, p_, t, opt), g, m, v, p)
        is_t = lambda x: isinstance(x, tuple)
        p2 = jax.tree.map(lambda o: o[0], out, is_leaf=is_t)
        m2 = jax.tree.map(lambda o: o[1], out, is_leaf=is_t)
        v2 = jax.tree.map(lambda o: o[2], out, is_leaf=is_t)
        return p2, m2, v2, loss, _norms(g), raw

    return jax.jit(one, donate_argnums=(0, 1, 2))


def steps(loss_fn, make_params, batches, opt):
    """Train ``len(batches)`` steps from the weights ``make_params()``
    returns (called twice: to start, and again to measure the change, so
    that no second copy lives through the steps).

    Returns {"loss": [per step], "grad": {leaf: norm of the first step's
    clipped gradient}, "raw_grad": {leaf: norm before clipping},
    "change": {leaf: norm of the parameters' change over all steps}}.
    """
    step = make_step(loss_fn, opt)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
    p = make_params()
    m, v = zeros(p), zeros(p)
    losses, grad, raw = [], None, None
    for i, tokens in enumerate(batches):
        p, m, v, loss, gn, rn = step(p, m, v, tokens, jnp.float32(i + 1))
        losses.append(float(loss))
        if grad is None:
            grad = {k: float(x) for k, x in gn.items()}
            raw = {k: float(x) for k, x in rn.items()}
    del m, v
    change = jax.jit(lambda a, b: _norms(jax.tree.map(jnp.subtract, a, b)))(
        p, make_params())
    return {"loss": losses, "grad": grad, "raw_grad": raw,
            "change": {k: float(x) for k, x in change.items()}}
