"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first jax use.

Single pod: 16×16 = 256 chips, axes (data, model) — the `model` axis is the
mesh minor axis so tensor-parallel collectives ride contiguous ICI links.
Multi-pod: 2×16×16 = 512 chips with the `pod` axis outermost — under the
default hybrid strategy only gradient/FSDP collectives cross the
(lower-bandwidth, DCN) pod boundary.
"""
from __future__ import annotations

import jax

from repro.core.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1, data: int | None = None, *,
                   stage: int = 1, axes_order=("data", "model")):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if data is None:
        data = n // (model * stage)
    if stage > 1:
        return make_mesh((stage, data, model), ("stage", "data", "model"))
    return make_mesh((data, model), axes_order)
