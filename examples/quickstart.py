"""Quickstart — the paper's Case 1 (pure data parallelism) plus the engine.

Runs on however many devices exist (set XLA_FLAGS for virtual CPUs)::

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

import repro as wh
from repro.configs import get_config
from repro.models.lm import build
from repro.optim import adamw

# ---- Case 1: replica scope around an arbitrary model fn -------------------
# wh.cluster owns the device mesh; wh.replica() marks the enclosed subgraph
# for data parallelism; wh.sub records it in the Whale IR.


def tiny_net(params, x):
    h = jax.nn.relu(x @ params["w1"])
    return h @ params["w2"]


key = jax.random.key(0)
params = {"w1": jax.random.normal(key, (32, 64)) * 0.1,
          "w2": jax.random.normal(key, (64, 8)) * 0.1}
x = jax.random.normal(key, (16, 32))

with wh.cluster() as cl:                       # mesh over all devices
    with wh.replica():
        out = wh.sub("net", tiny_net)(params, x)
print(f"[case 1] out {out.shape}; recorded "
      f"{len(cl.taskgraph.nodes)} subgraph(s): "
      f"{[n.name for n in cl.taskgraph.nodes]}, "
      f"flops={cl.taskgraph.nodes[0].flops:,}")

# ---- the engine on a real architecture -------------------------------------
cfg = get_config("tinyllama-1.1b", smoke=True)
model = build(cfg)
n_dev = len(jax.devices())
mesh = wh.make_mesh((n_dev, 1), ("data", "model")) if n_dev > 1 else \
    wh.make_mesh((1,), ("data",))
plan = wh.compile_plan(model, mesh)

opt = adamw(lr=1e-3)
batch = {"tokens": jnp.asarray(
    np.random.default_rng(0).integers(0, cfg.vocab, (8, 128)), jnp.int32)}
with mesh:
    params = plan.init_params(jax.random.key(0))
    opt_state = jax.jit(opt.init)(params)
    step = plan.jit_train_step(opt, batch, donate=False)
    for i in range(5):
        params, opt_state, m = step(params, opt_state, batch, i)
        print(f"[engine] step {i} loss {float(m['loss']):.4f}")
print("quickstart OK")
