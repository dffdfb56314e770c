"""Paper Case 4 / §3.1 — Bert-style training with pipeline × data parallel.

24 encoder layers are evenly partitioned into pipeline stages (the paper
used 3 stages over 24 layers; we use a CPU-sized bert-like config), stages
shard over a `stage` mesh axis, micro-batches flow with ppermute, and the
whole pipeline is replicated over the `data` axis — exactly Case 4:

    with wh.cluster():
      with wh.replica():
        with wh.pipeline(micro_batch=4):
          with wh.stage(): ...

Here the scopes configure the engine, and the executable schedule comes
from repro.core.pipeline (GPipe via shard_map + ppermute; DESIGN.md §2).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/bert_pipeline.py
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

import repro as wh
import repro.core.pipeline as pipe
from repro.configs import get_config
from repro.models.lm import build, model_graph
from repro.optim import adamw

MICRO = 4


def main():
    n = len(jax.devices())
    stages = 2 if n >= 2 else 1
    data_par = max(n // (stages * 2), 1)
    model_par = n // (stages * data_par)

    # bert-like: 4 layers (stands in for 24), gelu, LN — smoke-sized
    cfg = dataclasses.replace(
        get_config("stablelm-3b", smoke=True),
        n_layers=4, norm="ln", act="gelu", name="bert-like")
    model = build(cfg)

    mesh = wh.make_mesh((stages, data_par, model_par),
                        ("stage", "data", "model"))
    rules = wh.hybrid_rules(mesh)
    opt = adamw(lr=1e-3)

    # --- Case 4 scopes record the strategy into the IR ---
    with wh.cluster(mesh=mesh) as cl:
        with wh.replica():
            with wh.pipeline(micro_batch=MICRO):
                with wh.stage():
                    pass   # stage boundaries; executable schedule below
                with wh.stage():
                    pass
    strat = wh.strategy_from_taskgraph(cl)
    print(f"[case 4] mesh {dict(mesh.shape)} strategy {strat.describe()}")

    # --- executable pipelined train step (pick a schedule; uneven
    #     stage_layers also welcome here — see DESIGN.md §5) ---
    step = pipe.make_pipeline_train_step(model, mesh, rules, opt,
                                         micro_batches=MICRO,
                                         schedule="gpipe", donate=False)
    pspecs = pipe.staged_specs(rules, model.axes(), model.param_shapes())
    psh = jax.tree.map(lambda s: jax.NamedSharding(mesh, s), pspecs,
                       is_leaf=lambda t: isinstance(t, jax.sharding.PartitionSpec))
    with mesh:
        params = jax.jit(model.init, out_shardings=psh)(jax.random.key(0))
        opt_state = opt.init(params)
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab, (8, 128)),
            jnp.int32)
        losses = []
        for i in range(6):
            params, opt_state, loss = step(params, opt_state, tokens, i)
            losses.append(float(loss))
            print(f"  step {i} pipeline loss {losses[-1]:.4f}")
    assert losses[-1] < losses[0], "pipeline training must reduce loss"

    # --- the paper's Fig-2 headline from the cost model (64 V100s) ---
    from repro.core.cost_model import (V100_PAPER, StrategySpec,
                                       step_cost)
    bert = dataclasses.replace(get_config("stablelm-3b"), n_layers=24,
                               d_model=1024, n_heads=16, n_kv_heads=16,
                               d_ff=4096, vocab=30522, name="bert-large")
    meta = model_graph(bert, 512, 128).workload_meta()
    hdp = step_cost(meta, StrategySpec(dp=64, zero=0, remat=False,
                                       vocab_split=False), V100_PAPER,
                    overlap=0.0)            # Horovod: no overlap with bwd
    whale = step_cost(meta, StrategySpec(dp=16, pp=4, micro_batches=8,
                                         remat=False, vocab_split=False),
                      V100_PAPER, overlap=0.5)
    print(f"[fig2 headline] 64-GPU HDP {hdp.total*1e3:.0f} ms/step vs "
          f"Whale pipeline {whale.total*1e3:.0f} ms/step → "
          f"{hdp.total/whale.total:.2f}×")
    print("bert_pipeline OK")


if __name__ == "__main__":
    main()
