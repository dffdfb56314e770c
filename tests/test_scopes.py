"""The train step's named scopes (DESIGN.md §13) reach the compiled program.

Each layer boundary opens a ``jax.named_scope`` whose name is a contract
with whatever reads a profile: ``embed``, the mixer (``attention`` or
``ssd``), the MLP (``mlp`` or ``moe``), ``loss_head`` and ``optimizer``.
These tests compile the train step and the decode steps on the CPU at
smoke sizes and read the names back from the compiled HLO's op_names.
"""
from __future__ import annotations

import dataclasses
import re

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.planner import compile_plan
from repro.core.sharding import make_mesh
from repro.models.lm import build
from repro.optim.optimizer import adamw

NAMES = ("embed", "attention", "ssd", "mlp", "moe", "loss_head", "optimizer")
TOKEN = re.compile(r"(?:^|[/(])(%s)(?=[/)]|$)" % "|".join(NAMES))
INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%\S+ = .*? ([a-z][a-z0-9-]*)\(.*op_name="([^"]*)"')
NO_WORK = {"parameter", "tuple", "get-tuple-element", "constant", "bitcast"}
# the layer scan's own slicing, stacking and loop counter, outside any layer
SCAN_BOOKKEEPING = re.compile(r"/while/(?:body|cond)/[a-z_]+$")

CONFIGS = {
    "dense": (dataclasses.replace(get_config("qwen3-1.7b", smoke=True),
                                  attn_impl="pallas", xent_impl="pallas"),
              "attention", "mlp"),
    "ssd": (get_config("mamba2-1.3b", smoke=True), "ssd", None),
    "moe": (get_config("deepseek-moe-16b", smoke=True), "attention", "moe"),
}


def layer(op_name: str) -> str | None:
    found = TOKEN.findall(op_name)
    return found[-1] if found else None


def op_names(text: str) -> list[tuple[str, str]]:
    """(opcode, op_name) of each instruction of a compiled program's text
    that carries an op_name."""
    out = []
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            out.append((m.group(1), m.group(2)))
    return out


@pytest.fixture(scope="module")
def train_text():
    """{config key: the compiled train step's text}, compiled once."""
    mesh = make_mesh((1,), ("data",), devices=jax.devices()[:1])
    opt = adamw()
    tree = {"tokens": jax.ShapeDtypeStruct((2, 64), np.int32)}
    out = {}
    for key, (cfg, _, _) in CONFIGS.items():
        plan = compile_plan(build(cfg), mesh)
        params = plan.param_shapes
        with mesh:
            step = plan.jit_train_step(opt, tree)
            out[key] = step.lower(params, jax.eval_shape(opt.init, params),
                                  tree, np.int32(0)).compile().as_text()
    return out


@pytest.mark.parametrize("key", sorted(CONFIGS))
def test_train_step_carries_every_scope(train_text, key):
    _, mixer, mlp = CONFIGS[key]
    want = {"embed", mixer, "loss_head", "optimizer"} | ({mlp} - {None})
    found = {layer(n) for _, n in op_names(train_text[key])}
    assert want <= found, sorted(want - found)


@pytest.mark.parametrize("key", sorted(CONFIGS))
def test_scopes_cover_the_train_step(train_text, key):
    """At least 90% of the instructions that do work come from inside a
    scope, the layer scan's own bookkeeping aside."""
    rows = [n for op, n in op_names(train_text[key]) if op not in NO_WORK]
    rows = [n for n in rows if layer(n) or not SCAN_BOOKKEEPING.search(n)]
    scoped = sum(1 for n in rows if layer(n))
    assert scoped >= 0.9 * len(rows), (scoped, len(rows))


def test_fused_xent_backward_is_loss_head_bwd(train_text):
    """The fused cross-entropy's custom_vjp backward runs as a while loop;
    its ops read ``transpose(jvp(loss_head))/while/body/...``."""
    loop = [n for _, n in op_names(train_text["dense"])
            if "/while/body/" in n and layer(n) == "loss_head"
            and "transpose(" in n and "rematted_computation" not in n]
    assert loop
    assert all(re.search(r"transpose\(jvp\(loss_head\)\)/while/body/", n)
               for n in loop)


@pytest.mark.parametrize("key", sorted(CONFIGS))
def test_decode_step_carries_block_scopes(key):
    cfg, mixer, mlp = CONFIGS[key]
    model = build(cfg)
    params = model.param_shapes()
    tokens = jax.ShapeDtypeStruct((2,), np.int32)
    texts = [jax.jit(model.serve_step).lower(
        params, tokens, model.decode_state_shapes(2, 16)).compile().as_text()]
    if model.supports_paged:
        texts.append(jax.jit(model.serve_step_paged).lower(
            params, tokens, model.paged_state_shapes(2, 8, 8, 4))
            .compile().as_text())
    for text in texts:
        found = {layer(n) for _, n in op_names(text)}
        assert {mixer, mlp} - {None} <= found, sorted(found, key=str)
