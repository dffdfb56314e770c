"""Distributed integration tests — each runs in a subprocess with virtual
CPU devices (XLA device count is fixed at first jax import, so the main
pytest process stays single-device)."""

import functools

import pytest

from subproc import run_py as _run_py

# the snippets below want 8 devices unless they say otherwise
run_py = functools.partial(_run_py, devices=8)


def test_dp_matches_single_device_loss():
    """Data-parallel loss/grads == single-device (same params, same batch)."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.sharding import make_mesh
        from repro.configs import get_config
        from repro.core.planner import compile_plan
        from repro.models.lm import build
        cfg = get_config("qwen3-1.7b", smoke=True)
        model = build(cfg)
        params = model.init(jax.random.key(0))
        batch = {"tokens": jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab, (8, 64)),
            jnp.int32)}
        l_ref, _ = jax.jit(model.loss_fn)(params, batch)
        mesh = make_mesh((4, 2), ("data", "model"))
        plan = compile_plan(model, mesh)
        with mesh:
            l_dist, _ = plan.jit_loss(batch)(params, batch)
        np.testing.assert_allclose(float(l_ref), float(l_dist), rtol=2e-4)
        print("OK", float(l_ref), float(l_dist))
    """)


def test_gpipe_loss_matches_reference():
    """Pipeline (2 stages × dp × tp) loss == non-pipelined loss."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.sharding import make_mesh
        import repro as wh
        import repro.core.pipeline as pipe
        from repro.configs import get_config
        from repro.models.lm import build
        cfg = get_config("tinyllama-1.1b", smoke=True)
        model = build(cfg)
        mesh = make_mesh((2, 2, 2), ("stage", "data", "model"))
        rules = wh.hybrid_rules(mesh)
        lfn, pspecs = pipe.make_pipeline_loss(model, mesh, rules,
                                              micro_batches=4)
        psh = jax.tree.map(lambda s: jax.NamedSharding(mesh, s), pspecs,
                           is_leaf=lambda t: isinstance(
                               t, jax.sharding.PartitionSpec))
        with mesh:
            params = jax.jit(model.init, out_shardings=psh)(jax.random.key(0))
            tokens = jnp.asarray(np.random.default_rng(0).integers(
                0, cfg.vocab, (8, 64)), jnp.int32)
            l_pipe = jax.jit(lfn)(params, tokens)
        l_ref, _ = jax.jit(model.loss_fn)(
            model.init(jax.random.key(0)), {"tokens": tokens})
        np.testing.assert_allclose(float(l_pipe), float(l_ref), rtol=2e-3)
        print("OK", float(l_pipe), float(l_ref))
    """)


def test_gpipe_training_reduces_loss():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.sharding import make_mesh
        import repro as wh
        import repro.core.pipeline as pipe
        from repro.configs import get_config
        from repro.models.lm import build
        from repro.optim import adamw
        cfg = get_config("tinyllama-1.1b", smoke=True)
        model = build(cfg)
        mesh = make_mesh((2, 2, 1), ("stage", "data", "model"))
        rules = wh.hybrid_rules(mesh)
        opt = adamw(lr=1e-3)
        step = pipe.make_pipeline_train_step(model, mesh, rules, opt,
                                             micro_batches=2, donate=False)
        pspecs = pipe.staged_specs(rules, model.axes(), model.param_shapes())
        psh = jax.tree.map(lambda s: jax.NamedSharding(mesh, s), pspecs,
                           is_leaf=lambda t: isinstance(
                               t, jax.sharding.PartitionSpec))
        with mesh:
            params = jax.jit(model.init, out_shardings=psh)(jax.random.key(0))
            ost = opt.init(params)
            tokens = jnp.asarray(np.random.default_rng(0).integers(
                0, cfg.vocab, (8, 64)), jnp.int32)
            losses = []
            for i in range(4):
                params, ost, loss = step(params, ost, tokens, i)
                losses.append(float(loss))
        assert losses[-1] < losses[0], losses
        print("OK", losses)
    """)


def test_uneven_hetero_plan_pipeline_matches_reference():
    """The tentpole acceptance path: a mixed V100/P100 ClusterSpec →
    hetero planner emits an uneven latency-equalizing stage allocation →
    the plan's pipeline step executes it end to end (padded stage-sharded
    params, 1F1B schedule on the strategy) and the loss matches the
    single-device reference."""
    run_py("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.core.cost_model import (ClusterSpec, DeviceGroup,
                                           P100_16G, StrategySpec,
                                           V100_PAPER)
        from repro.core.planner import compile_plan, mesh_for_strategy
        from repro.models.lm import build, model_graph
        from repro.optim import adamw
        import repro.core.pipeline as pipe
        cfg = dataclasses.replace(get_config("tinyllama-1.1b", smoke=True),
                                  n_layers=8)
        model = build(cfg)
        meta = model_graph(cfg, 64, 512).workload_meta()   # planning scale
        spec = ClusterSpec(groups=(DeviceGroup("v100", V100_PAPER, 4),
                                   DeviceGroup("p100", P100_16G, 4)))
        strat = StrategySpec(dp=2, pp=4, micro_batches=4, schedule="1f1b")
        mesh = mesh_for_strategy(strat)
        plan = compile_plan(model, mesh, strategy=strat, cluster_spec=spec,
                            workload_meta=meta, overlap=0.5)
        sl = plan.stage_layers()
        assert sum(sl) == 8 and len(set(sl)) > 1, f"expected uneven: {sl}"
        opt = adamw(lr=1e-3)
        step = plan.jit_pipeline_train_step(opt, donate=False)
        params = plan.init_pipeline_params(jax.random.key(0))
        tokens = jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab, (8, 64)), jnp.int32)
        with mesh:
            ost = jax.jit(opt.init)(params)
            lfn, _ = pipe.make_pipeline_loss(
                model, mesh, plan.rules, micro_batches=4, stage_layers=sl)
            l_pipe = jax.jit(lfn)(params, tokens)
            losses = []
            for i in range(3):
                params, ost, loss = step(params, ost, tokens, i)
                losses.append(float(loss))
        l_ref, _ = jax.jit(model.loss_fn)(
            model.init(jax.random.key(0)), {"tokens": tokens})
        np.testing.assert_allclose(float(l_pipe), float(l_ref), rtol=2e-3)
        np.testing.assert_allclose(losses[0], float(l_ref), rtol=2e-3)
        assert losses[-1] < losses[0], losses
        print("OK", sl, float(l_pipe), float(l_ref), losses)
    """)


def test_compress_pod_training_step():
    """Cross-pod int8 error-feedback gradient reduction end-to-end."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.sharding import make_mesh
        from repro.configs import get_config
        from repro.core.planner import compile_plan, mesh_for_strategy
        from repro.core.cost_model import StrategySpec
        from repro.models.lm import build
        from repro.optim import adamw
        from repro.optim import grad_compress
        cfg = get_config("tinyllama-1.1b", smoke=True)
        model = build(cfg)
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        plan = compile_plan(model, mesh)
        opt = adamw(lr=1e-3)
        batch = {"tokens": jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab, (8, 64)), jnp.int32)}
        with mesh:
            params = plan.init_params(jax.random.key(0))
            ost = opt.init(params)
            err = grad_compress.init_error_tree(params)
            step = plan.jit_train_step(opt, batch, compress_pod=True,
                                       donate=False)
            losses = []
            for i in range(4):
                params, ost, m, err = step(params, ost, batch, i, err)
                losses.append(float(m["loss"]))
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0], losses
        print("OK", losses)
    """)


def test_expert_parallel_moe_matches_reference():
    """Tentpole acceptance: the nested replica{split[experts]} executor —
    moe_block_ep's shard_map with explicit all-to-all dispatch/combine
    bridges — equals the single-device moe_block to fp32 tolerance,
    forward AND backward (the shard_map is fully manual over the expert
    axis)."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.sharding import make_mesh
        from repro.models.moe import (MoECfg, init_moe, moe_block,
                                      moe_block_ep)
        cfg = MoECfg(d_model=32, n_experts=8, top_k=2, d_ff_expert=64,
                     n_shared=1)
        params = init_moe(jax.random.key(0), cfg, jnp.float32)
        x = jax.random.normal(jax.random.key(1), (8, 16, 32), jnp.float32)
        mesh = make_mesh((4,), ("expert",))

        y_ref, aux_ref = jax.jit(lambda p, x: moe_block(p, x, cfg))(params, x)
        with mesh:
            y_ep, aux_ep = jax.jit(
                lambda p, x: moe_block_ep(p, x, cfg, mesh))(params, x)
        np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_ep),
                                   rtol=2e-5, atol=2e-5)
        for k in ("lb_loss", "z_loss"):
            np.testing.assert_allclose(float(aux_ref[k]), float(aux_ep[k]),
                                       rtol=1e-5)

        def loss(block):
            def f(p, x):
                y, aux = block(p, x)
                return (y ** 2).mean() + aux["lb_loss"] + aux["z_loss"]
            return f
        g_ref = jax.jit(jax.grad(loss(lambda p, x: moe_block(p, x, cfg))))(
            params, x)
        with mesh:
            g_ep = jax.jit(jax.grad(loss(
                lambda p, x: moe_block_ep(p, x, cfg, mesh))))(params, x)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=3e-4, atol=1e-6), g_ref, g_ep)
        print("OK ep fwd+bwd == reference")
    """, devices=4)


def test_expert_parallel_rejects_indivisible():
    run_py("""
        import jax, jax.numpy as jnp
        from repro.core.sharding import make_mesh
        from repro.models.moe import MoECfg, init_moe, moe_block_ep
        cfg = MoECfg(d_model=16, n_experts=6, top_k=2, d_ff_expert=32)
        params = init_moe(jax.random.key(0), cfg, jnp.float32)
        mesh = make_mesh((4,), ("expert",))
        try:
            moe_block_ep(params, jnp.ones((8, 16, 16)), cfg, mesh)
        except ValueError as e:
            assert "n_experts" in str(e), e
            print("OK raises on E % ep != 0")
        else:
            raise SystemExit("expected ValueError")
    """, devices=4)


def test_elastic_remesh_roundtrip(tmp_path):
    """Checkpoint on a 4×1 mesh, restore on 2×2 — values identical."""
    run_py(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.sharding import make_mesh
        from repro.ckpt.checkpoint import CheckpointManager
        from repro.configs import get_config
        from repro.core.planner import compile_plan
        from repro.models.lm import build
        from repro.optim import adamw
        from repro.runtime.elastic import ElasticContext
        cfg = get_config("qwen3-1.7b", smoke=True)
        model = build(cfg)
        opt = adamw(lr=1e-3)
        mesh1 = make_mesh((4, 1), ("data", "model"))
        plan1 = compile_plan(model, mesh1)
        with mesh1:
            params = plan1.init_params(jax.random.key(1))
            ost = opt.init(params)
        mgr = CheckpointManager({str(tmp_path)!r}, keep=2)
        mgr.save(7, {{"params": params, "opt": ost}}, extra={{"k": 1}})
        mesh2 = make_mesh((2, 2), ("data", "model"))
        ctx = ElasticContext(model=model, optimizer=opt)
        step, plan2, p2, o2, extra = ctx.remesh(mgr, mesh2)
        assert step == 7 and extra["k"] == 1
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b))
        # restored params actually usable on the new mesh
        batch = {{"tokens": jnp.zeros((4, 32), jnp.int32)}}
        with mesh2:
            loss, _ = plan2.jit_loss(batch)(p2, batch)
        assert np.isfinite(float(loss))
        print("OK")
    """)


@pytest.mark.slow
def test_production_dryrun_one_cell():
    """The real 256-chip dry-run machinery on one (arch × shape) cell."""
    out = run_py("""
        from repro.launch.dryrun import run_cell
        rec = run_cell("tinyllama-1.1b", "decode_32k")
        assert rec["status"] == "ok", rec
        assert rec["mem_temp_gib"] + rec["mem_args_gib"] < 16.0
        assert rec["coll_bytes_per_dev"] > 0
        assert rec["flops_per_dev"] > 0
        print("OK", rec["bottleneck"], round(rec["roofline_frac"], 4))
    """, devices=8)   # XLA_FLAGS overridden inside dryrun to 512
    assert "OK" in out


# ---------------------------------------------------------------------------
# encoder–decoder two-tower pipeline (PR 9: the M6 multimodal cut)
# ---------------------------------------------------------------------------

def test_encdec_pipeline_loss_matches_reference():
    """Two-tower pipeline (stage 0 = frontend+encoder, stage 1 = decoder)
    loss == the non-pipelined encdec loss.  Forward-only, so it runs on
    every supported jax (the grad path is gated below)."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.sharding import make_mesh
        import repro as wh
        import repro.core.pipeline as pipe
        from repro.configs import get_config
        from repro.models.lm import build
        cfg = get_config("seamless-m4t-medium", smoke=True)
        model = build(cfg)
        mesh = make_mesh((2, 1, 1), ("stage", "data", "model"))
        rules = wh.hybrid_rules(mesh)
        lfn, pspecs = pipe.make_encdec_pipeline_loss(model, mesh, rules,
                                                     micro_batches=2)
        rng = np.random.default_rng(0)
        frames = jnp.asarray(rng.normal(size=(4, 8, cfg.d_model)),
                             jnp.float32)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)), jnp.int32)
        params = model.init(jax.random.key(0))
        with mesh:
            l_pipe = jax.jit(lfn)(params, frames, tokens)
        l_ref, _ = jax.jit(model.loss_fn)(
            params, {"frames": frames, "tokens": tokens})
        np.testing.assert_allclose(float(l_pipe), float(l_ref), rtol=2e-4)
        print("OK", float(l_pipe), float(l_ref))
    """, devices=2)


def test_encdec_pipeline_rejects_wrong_stage_count():
    run_py("""
        import jax
        from repro.core.sharding import make_mesh
        import repro as wh
        import repro.core.pipeline as pipe
        from repro.configs import get_config
        from repro.models.lm import build
        model = build(get_config("seamless-m4t-medium", smoke=True))
        mesh = make_mesh((4, 1, 1), ("stage", "data", "model"))
        try:
            pipe.make_encdec_pipeline_loss(model, mesh,
                                           wh.hybrid_rules(mesh),
                                           micro_batches=2)
        except ValueError as e:
            assert "2-stage" in str(e)
            print("OK")
        else:
            raise SystemExit("4-stage encdec should have been rejected")
    """, devices=4)


def test_encdec_plan_routes_to_two_tower_engine():
    """compile_plan on an encdec arch: stage_layers() reports the fixed
    tower edge and jit_pipeline_train_step dispatches to the encdec
    engine (no layer-stack splitting)."""
    run_py("""
        import jax
        from repro.configs import get_config
        from repro.core.cost_model import StrategySpec
        from repro.core.planner import compile_plan, mesh_for_strategy
        from repro.models.lm import build
        cfg = get_config("seamless-m4t-medium", smoke=True)
        model = build(cfg)
        assert model.stack is None     # encdec has no repeated layer stack
        strat = StrategySpec(dp=1, pp=2, micro_batches=2)
        mesh = mesh_for_strategy(strat)
        plan = compile_plan(model, mesh, strategy=strat)
        assert plan.stage_layers() == (cfg.n_enc_layers, cfg.n_dec_layers), \
            plan.stage_layers()
        print("OK", plan.stage_layers())
    """, devices=2)


def test_encdec_pipeline_training_reduces_loss():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.core.cost_model import StrategySpec
        from repro.core.planner import compile_plan, mesh_for_strategy
        from repro.models.lm import build
        from repro.optim import adamw
        cfg = get_config("seamless-m4t-medium", smoke=True)
        model = build(cfg)
        strat = StrategySpec(dp=1, pp=2, micro_batches=2)
        mesh = mesh_for_strategy(strat)
        plan = compile_plan(model, mesh, strategy=strat)
        opt = adamw(lr=1e-3)
        step = plan.jit_pipeline_train_step(opt, donate=False)
        params = plan.init_pipeline_params(jax.random.key(0))
        rng = np.random.default_rng(0)
        frames = jnp.asarray(rng.normal(size=(4, 8, cfg.d_model)),
                             jnp.float32)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)), jnp.int32)
        with mesh:
            ost = jax.jit(opt.init)(params)
            losses = []
            for i in range(4):
                params, ost, loss = step(params, ost, frames, tokens,
                                         jnp.asarray(i))
                losses.append(float(loss))
        assert losses[-1] < losses[0], losses
        print("OK", losses)
    """, devices=2)


def test_multimodal_pipeline_determinism_and_reshard():
    """MultimodalPipeline: modality stream is deterministic, resumable,
    and host-count invariant (the token-pipeline guarantees extend to
    frames/patch_embeds)."""
    import numpy as np
    from repro.data.pipeline import DataCfg, MultimodalPipeline
    cfg = DataCfg(global_batch=8, seq_len=16, vocab=512, seed=3)
    p1 = MultimodalPipeline(cfg, modality="encdec", d_model=32, src_len=8,
                            host_id=0, n_hosts=1)
    batches = [p1.next_batch() for _ in range(4)]
    assert batches[0]["frames"].shape == (8, 8, 32)
    # determinism: a fresh pipeline replays the same stream
    p2 = MultimodalPipeline(cfg, modality="encdec", d_model=32, src_len=8,
                            host_id=0, n_hosts=1)
    for b in batches:
        b2 = p2.next_batch()
        np.testing.assert_array_equal(b["tokens"], b2["tokens"])
        np.testing.assert_array_equal(b["frames"], b2["frames"])
    # reshard: 2-host shards concatenate to the 1-host batch
    h0 = p1.reshard(host_id=0, n_hosts=2)
    h1 = p1.reshard(host_id=1, n_hosts=2)
    full = p1.next_batch()
    a, b = h0.next_batch(), h1.next_batch()
    np.testing.assert_array_equal(
        np.concatenate([a["frames"], b["frames"]]), full["frames"])
    np.testing.assert_array_equal(
        np.concatenate([a["tokens"], b["tokens"]]), full["tokens"])
    # vlm modality emits patch_embeds of the frontend length
    pv = MultimodalPipeline(cfg, modality="vlm", d_model=32, frontend_len=4,
                            host_id=0, n_hosts=1)
    assert pv.next_batch()["patch_embeds"].shape == (8, 4, 32)
