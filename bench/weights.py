"""Seeded weights, made on the device in one jitted call.

The layout (leaf path → shape) and each leaf's distribution come from the
configuration's reference module (``shapes`` and ``init_rules``), so the
program under test and the reference receive the same weights from the
same seed, and neither made them.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int) -> tuple:
    """A non-negative seed (also one past 32 bits) as two uint32 words,
    passed to the jitted makers as arguments so that every seed runs the
    same compiled program."""
    return np.uint32(seed & 0xFFFFFFFF), np.uint32((seed >> 32) & 0xFFFFFFFF)


def leaf_key(lo, hi, path: str) -> jax.Array:
    """The key of one weight leaf."""
    key = jax.random.fold_in(jax.random.key(lo), hi)
    return jax.random.fold_in(jax.random.fold_in(key, 1),
                              zlib.crc32(path.encode()))


def nest(flat: dict) -> dict:
    """{"a/b/c": x} → {"a": {"b": {"c": x}}}."""
    out: dict = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return out


def flatten(tree, prefix: str = "") -> dict:
    """Inverse of :func:`nest` for nested dicts."""
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, path))
        else:
            out[path] = v
    return out


def leaf(key, shape, rule):
    kind, scale = rule
    if kind == "normal":
        return jax.random.normal(key, shape, jnp.float32) * scale
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if kind == "log_linspace":       # log(linspace(1, scale, last dim))
        row = jnp.log(jnp.linspace(1.0, scale, shape[-1], dtype=jnp.float32))
        return jnp.broadcast_to(row, shape)
    raise ValueError(f"unknown weight rule {kind!r}")


def make(shapes: dict, rules: dict, seed: int, shardings=None,
         device=None):
    """Nested float32 weights for ``shapes`` ({path: shape}) from ``seed``.

    ``shardings``: an optional nested tree of shardings matching the
    result, else everything on ``device``; the weights are made directly
    into them."""
    if set(shapes) != set(rules):
        raise ValueError(f"weight rules do not cover the layout: "
                         f"{sorted(set(shapes) ^ set(rules))}")
    if shardings is None and device is not None:
        shardings = jax.sharding.SingleDeviceSharding(device)

    def build(lo, hi):
        return nest({p: leaf(leaf_key(lo, hi, p), tuple(s), rules[p])
                     for p, s in shapes.items()})

    return jax.jit(build, out_shardings=shardings)(*seed_words(seed))


def check_layout(shapes: dict, program_tree) -> None:
    """Fail loudly where the program's parameter tree differs from the
    layout the reference reads."""
    got = {p: tuple(v.shape) for p, v in flatten(program_tree).items()}
    want = {p: tuple(s) for p, s in shapes.items()}
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))
        raise ValueError(f"program parameter layout differs from the "
                         f"reference's: {diff[:8]}")
