"""Engine check on 8 virtual CPU devices: planner train step, gpipe step, auto search."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

import repro as wh
from repro.configs import get_config
import repro.core.pipeline as pipe
from repro.core.planner import compile_plan
from repro.models.lm import build
from repro.optim.optimizer import adamw

cfg = get_config("tinyllama-1.1b", smoke=True)
model = build(cfg)
opt = adamw(lr=1e-3)

# ---- 1. GSPMD hybrid plan: dp=4 × tp=2 ----
mesh = wh.make_mesh((4, 2), ("data", "model"))
plan = compile_plan(model, mesh)
params = plan.init_params(jax.random.key(0))
opt_state = jax.jit(opt.init, out_shardings=wh.core.planner._ns(mesh, plan.opt_specs(opt)) if False else None)(params) if False else opt.init(params)
batch = {"tokens": jnp.asarray(np.random.randint(0, cfg.vocab, (8, 64)), jnp.int32)}
with mesh:
    step = plan.jit_train_step(opt, batch, micro_batches=2, donate=False)
    p2, o2, metrics = step(params, opt_state, batch, 0)
print("hybrid train:", {k: float(v) for k, v in metrics.items() if v.ndim == 0})
assert np.isfinite(metrics["loss"])

# losses decrease over a few steps
with mesh:
    p, o = params, opt_state
    for i in range(5):
        p, o, m = step(p, o, batch, i)
    print("loss step0 -> step5:", float(metrics["loss"]), "->", float(m["loss"]))
    assert m["loss"] < metrics["loss"]

# ---- 2. serve step ----
with mesh:
    serve = plan.jit_serve_step(batch=8, cache_len=32, donate=False)
    state = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         model.decode_state_shapes(8, 32))
    logits, st2 = serve(params, jnp.zeros((8,), jnp.int32), state)
print("serve ok:", logits.shape)

# ---- 3. pipeline: 2 stages × dp=2 × tp=2 ----
mesh3 = wh.make_mesh((2, 2, 2), ("stage", "data", "model"))
rules = wh.hybrid_rules(mesh3)
plan3 = compile_plan(model, mesh3)
with mesh3:
    pstep = pipe.make_pipeline_train_step(model, mesh3, rules, opt,
                                          micro_batches=4, donate=False)
    # params sharded for pipeline
    pspecs = pipe.staged_specs(rules, model.axes(), model.param_shapes())
    psh = jax.tree.map(lambda s: jax.NamedSharding(mesh3, s), pspecs,
                       is_leaf=lambda t: isinstance(t, jax.sharding.PartitionSpec))
    params3 = jax.jit(model.init, out_shardings=psh)(jax.random.key(0))
    ost3 = opt.init(params3)
    tokens = batch["tokens"]
    p3, o3, loss3 = pstep(params3, ost3, tokens, 0)
print("gpipe loss:", float(loss3))
assert np.isfinite(float(loss3))

# pipeline loss == non-pipeline loss on same params (both from key 0)
with mesh:
    l_ref, _ = plan.jit_loss(batch)(params, batch)
# ref loss includes z_loss etc; compare
lfn, _ = pipe.make_pipeline_loss(model, mesh3, rules, micro_batches=4)
with mesh3:
    l_pipe = jax.jit(lfn)(params3, tokens)
print("ref loss:", float(l_ref), "pipe loss:", float(l_pipe))
np.testing.assert_allclose(float(l_ref), float(l_pipe), rtol=2e-2)

# ---- 4. auto-parallel search ----
meta = wh.model_graph(get_config("tinyllama-1.1b"), 256, 4096).workload_meta()
cands = wh.search(meta, 256, top_k=5)
for c in cands:
    print(f"  {c.strategy.describe():40s} t={c.total*1e3:8.1f} ms "
          f"mem={c.cost.mem_bytes/2**30:.1f} GiB")
print("ENGINE OK")
