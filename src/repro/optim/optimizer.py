"""Optimizers with sharding-aware state and dtype policies.

Minimal optax-like interface (no optax dependency):

    opt = adamw(lr=..., moment_dtype="bfloat16")
    state = opt.init(params)
    params, state = opt.apply(grads, state, params, step)
    state_axes = opt.state_axes(param_axes)   # for the planner's ZeRO sharding

AdamW state dtype is configurable (bf16 moments for the giant archs);
Adafactor keeps factored second moments (O(N/d) state — the production choice
for grok-scale models on 16 GB HBM parts, see configs/grok_1_314b.py).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp


def _tmap(f, *trees, **kw):
    return jax.tree.map(f, *trees, **kw)


@dataclasses.dataclass(frozen=True)
class Schedule:
    base_lr: float = 3e-4
    warmup: int = 100
    decay_steps: int = 10000
    min_ratio: float = 0.1

    def __call__(self, step):
        step = jnp.asarray(step, jnp.float32)
        warm = jnp.minimum(step / jnp.maximum(self.warmup, 1), 1.0)
        frac = jnp.clip((step - self.warmup)
                        / jnp.maximum(self.decay_steps - self.warmup, 1), 0, 1)
        cos = 0.5 * (1 + jnp.cos(jnp.pi * frac))
        return self.base_lr * warm * (self.min_ratio + (1 - self.min_ratio) * cos)


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(tree)))


def clip_by_global_norm(grads, max_norm: float):
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return _tmap(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype),
                 grads), norm


@dataclasses.dataclass(frozen=True)
class Optimizer:
    init: Callable
    apply: Callable                 # (grads, state, params, step) -> (params, state)
    state_axes: Callable            # param_axes -> state axes tree
    name: str = "opt"


def adamw(lr: Schedule | float = 3e-4, b1: float = 0.9, b2: float = 0.95,
          eps: float = 1e-8, weight_decay: float = 0.0,
          moment_dtype: str = "float32", max_grad_norm: float = 1.0) -> Optimizer:
    sched = lr if callable(lr) else (lambda s: jnp.asarray(lr, jnp.float32))
    mdt = jnp.dtype(moment_dtype)

    def init(params):
        zeros = _tmap(lambda p: jnp.zeros(p.shape, mdt), params)
        return {"mu": zeros,
                "nu": _tmap(lambda p: jnp.zeros(p.shape, mdt), params)}

    @jax.named_scope("optimizer")
    def apply(grads, state, params, step):
        if max_grad_norm:
            grads, _ = clip_by_global_norm(grads, max_grad_norm)
        t = jnp.asarray(step, jnp.float32) + 1.0
        lr_t = sched(step)
        c1 = 1 - b1 ** t
        c2 = 1 - b2 ** t

        def upd(g, mu, nu, p):
            g = g.astype(jnp.float32)
            mu_n = b1 * mu.astype(jnp.float32) + (1 - b1) * g
            nu_n = b2 * nu.astype(jnp.float32) + (1 - b2) * g * g
            u = (mu_n / c1) / (jnp.sqrt(nu_n / c2) + eps)
            if weight_decay:
                u = u + weight_decay * p.astype(jnp.float32)
            p_n = p.astype(jnp.float32) - lr_t * u
            return p_n.astype(p.dtype), mu_n.astype(mdt), nu_n.astype(mdt)

        out = _tmap(upd, grads, state["mu"], state["nu"], params)
        new_params = _tmap(lambda o: o[0], out, is_leaf=lambda x: isinstance(x, tuple))
        new_mu = _tmap(lambda o: o[1], out, is_leaf=lambda x: isinstance(x, tuple))
        new_nu = _tmap(lambda o: o[2], out, is_leaf=lambda x: isinstance(x, tuple))
        return new_params, {"mu": new_mu, "nu": new_nu}

    def state_axes(param_axes):
        return {"mu": param_axes, "nu": param_axes}

    return Optimizer(init=init, apply=apply, state_axes=state_axes, name="adamw")


def adafactor(lr: Schedule | float = 3e-4, decay: float = 0.8, eps: float = 1e-30,
              clip_threshold: float = 1.0, max_grad_norm: float = 1.0) -> Optimizer:
    """Factored second-moment optimizer (Shazeer & Stern) — O(N/d) state."""
    sched = lr if callable(lr) else (lambda s: jnp.asarray(lr, jnp.float32))

    def _factored(shape) -> bool:
        # ndim-based so it matches state_axes (which only sees axis names);
        # size-1 dims factor fine (vr/vc just carry the singleton)
        return len(shape) >= 2

    def init(params):
        def one(p):
            if _factored(p.shape):
                return {"vr": jnp.zeros(p.shape[:-1], jnp.float32),
                        "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:], jnp.float32)}
            return {"v": jnp.zeros(p.shape, jnp.float32)}
        return {"v": _tmap(one, params)}

    def apply(grads, state, params, step):
        if max_grad_norm:
            grads, _ = clip_by_global_norm(grads, max_grad_norm)
        t = jnp.asarray(step, jnp.float32) + 1.0
        beta2 = 1.0 - t ** (-decay)
        lr_t = sched(step)

        def upd(g, v, p):
            g = g.astype(jnp.float32)
            g2 = g * g + eps
            if "vr" in v:
                vr = beta2 * v["vr"] + (1 - beta2) * g2.mean(-1)
                vc = beta2 * v["vc"] + (1 - beta2) * g2.mean(-2)
                rms = (vr[..., None] * vc[..., None, :]
                       / jnp.maximum(vr.mean(-1)[..., None, None], eps))
                u = g * jax.lax.rsqrt(rms + eps)
                v_new = {"vr": vr, "vc": vc}
            else:
                vv = beta2 * v["v"] + (1 - beta2) * g2
                u = g * jax.lax.rsqrt(vv + eps)
                v_new = {"v": vv}
            if clip_threshold:
                un = jnp.sqrt(jnp.mean(u * u))
                u = u / jnp.maximum(1.0, un / clip_threshold)
            p_n = p.astype(jnp.float32) - lr_t * u
            return p_n.astype(p.dtype), v_new

        leaves, treedef = jax.tree.flatten(params)
        gl = treedef.flatten_up_to(grads)
        vl = treedef.flatten_up_to(state["v"])
        out = [upd(g, v, p) for g, v, p in zip(gl, vl, leaves)]
        new_params = treedef.unflatten([o[0] for o in out])
        new_v = treedef.unflatten([o[1] for o in out])
        return new_params, {"v": new_v}

    def state_axes(param_axes):
        def one(names):
            names = tuple(names)
            if len(names) >= 2:
                return {"vr": names[:-1], "vc": names[:-2] + names[-1:]}
            return {"v": names}
        return {"v": jax.tree.map(one, param_axes,
                                  is_leaf=lambda t: isinstance(t, tuple))}

    return Optimizer(init=init, apply=apply, state_axes=state_axes,
                     name="adafactor")


def sgd(lr: float = 1e-2) -> Optimizer:
    def init(params):
        return {}

    def apply(grads, state, params, step):
        return _tmap(lambda p, g: (p.astype(jnp.float32)
                                   - lr * g.astype(jnp.float32)).astype(p.dtype),
                     params, grads), state

    def state_axes(param_axes):
        return {}

    return Optimizer(init=init, apply=apply, state_axes=state_axes, name="sgd")


def get_optimizer(name: str, **kw) -> Optimizer:
    return {"adamw": adamw, "adafactor": adafactor, "sgd": sgd}[name](**kw)
