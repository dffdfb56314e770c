"""Self-healing elastic runtime: straggler → evict → rebalance → resume.

Unit layers (monitor seeding/one-shot, aggregator eviction, fault
injector, cooperative loop stop, cluster shrinking, exactly-once data)
run in-process; the end-to-end controller scenarios run in subprocesses
with virtual CPU devices (XLA device count is fixed at first jax import).
"""

import numpy as np
import pytest

from repro.ckpt.checkpoint import CheckpointManager
from repro.core.cost_model import (ClusterSpec, DeviceGroup, P100_16G,
                                   StrategySpec, T4_16G, TPU_V5E,
                                   V100_PAPER)
from repro.core.hetero import shrink_cluster
from repro.data.pipeline import DataCfg, TokenPipeline
from repro.runtime.elastic import HostTopology, SimHost, shrink_devices
from repro.runtime.fault_tolerance import FaultTolerantLoop
from repro.runtime.faults import (CrashStep, FaultInjector, SimClock,
                                  SlowHost)
from repro.runtime.straggler import HostStragglerAggregator, StragglerMonitor

from subproc import run_py


# ---------------------------------------------------------------------------
# StragglerMonitor: warmup variance seeding + one-shot flag
# ---------------------------------------------------------------------------

def test_monitor_seeds_variance_from_warmup():
    """A post-warmup sample inside the warmup spread must NOT be an
    outlier.  The pre-fix monitor left var=0 after warmup, so the first
    comparison ran against the 5%-of-mean floor and flagged normal
    jitter."""
    m = StragglerMonitor(threshold=2.0, patience=1, warmup=5)
    for dt in (1.0, 1.2, 0.9, 1.1, 1.0):
        assert not m.observe(dt)
    assert m.var > 0.0, "warmup must seed the variance"
    # mean≈1.04, std≈0.114 → threshold ≈ 1.27; 1.25 is within spread
    # (under var=0 the floor gives threshold ≈ 1.14 → spurious flag)
    assert not m.observe(1.25)
    assert m.consecutive == 0 and not m.flagged


def test_monitor_one_shot_flag_and_reset():
    m = StragglerMonitor(threshold=2.0, patience=2, warmup=3)
    for _ in range(3):
        m.observe(1.0)
    assert not m.observe(5.0)          # first outlier: patience not met
    assert m.observe(5.0)              # second: flag trips → True ONCE
    assert m.flagged
    for _ in range(5):
        assert not m.observe(5.0)      # latched, never re-reported
    assert m.flagged
    m.reset()                          # re-arm, stats kept
    assert not m.flagged and m.n > 0
    assert not m.observe(5.0)
    assert m.observe(5.0)              # flags again after re-arm
    m.reset(clear_stats=True)
    assert m.n == 0 and m.var == 0.0


def test_monitor_constant_warmup_still_detects():
    """Zero-variance warmup (identical times) falls back to the
    5%-of-mean floor and still detects a genuine 2× straggler."""
    m = StragglerMonitor(threshold=2.0, patience=2, warmup=3)
    for _ in range(3):
        m.observe(0.1)
    assert not m.observe(0.2)
    assert m.observe(0.2)


# ---------------------------------------------------------------------------
# HostStragglerAggregator: no re-reporting, eviction, reset
# ---------------------------------------------------------------------------

def test_aggregator_reports_once_and_respects_eviction():
    agg = HostStragglerAggregator(n_hosts=4, patience=2, warmup=3)
    reported = []
    for step in range(20):
        times = {h: 0.1 for h in range(4)}
        if step >= 6:
            times[2] = 0.4
        reported.extend(agg.observe(times))
    # the pre-fix aggregator re-reported host 2 on every call after the
    # flag; one-shot semantics report it exactly once
    assert reported == [2]
    agg.evict(2)
    assert 2 not in agg.monitors and 2 in agg.evicted
    # the dying host may keep emitting heartbeats — ignored
    assert agg.observe({h: (0.4 if h == 2 else 0.1) for h in range(4)}) == []


def test_aggregator_reset_renumbers_survivors():
    agg = HostStragglerAggregator(n_hosts=3, patience=2, warmup=2)
    agg.evict(1)
    agg.reset([0, 2])
    assert sorted(agg.monitors) == [0, 2]
    agg.reset([0, 1, 2])               # evicted host stays excluded
    assert sorted(agg.monitors) == [0, 2]


# ---------------------------------------------------------------------------
# fault injector: deterministic clock, crash budget, sim clock
# ---------------------------------------------------------------------------

def test_injector_deterministic_and_slow_factor():
    inj = FaultInjector(scenarios=(SlowHost(host=1, start_step=5,
                                            factor=3.0),),
                        n_hosts=2, seed=42)
    inj2 = FaultInjector(scenarios=(SlowHost(host=1, start_step=5,
                                             factor=3.0),),
                         n_hosts=2, seed=42)
    for step in (0, 4, 5, 9):
        assert inj.host_times(step, base=0.1) == inj2.host_times(step,
                                                                 base=0.1)
    before = inj.host_times(4, base=0.1)
    after = inj.host_times(5, base=0.1)
    assert abs(before[1] / before[0] - 1.0) < 0.2       # jitter only
    assert after[1] / after[0] > 2.0                    # 3× straggler


def test_injector_nominal_clock_ignores_measured_base():
    """With a nominal step time the timeline is a pure function of
    (seed, step, host) — load spikes in the measured base can't leak in."""
    inj = FaultInjector(n_hosts=2, nominal=0.05)
    assert inj.host_times(3, base=99.0) == inj.host_times(3, base=0.001)
    assert 0.04 < inj.host_times(3, base=99.0)[0] < 0.06


def test_injector_crash_budget_and_clock():
    inj = FaultInjector(scenarios=(CrashStep(step=3, times=2),), n_hosts=1)
    inj.maybe_fail(2)                                   # no-op
    for _ in range(2):
        with pytest.raises(RuntimeError, match="injected"):
            inj.maybe_fail(3)
    inj.maybe_fail(3)                                   # budget exhausted
    clock = SimClock()
    clock.advance({0: 0.1, 1: 0.4})
    clock.charge(1.0)
    assert clock.t == pytest.approx(1.4) and clock.steps == 1


# ---------------------------------------------------------------------------
# FaultTolerantLoop: cooperative stop + step-aware extra_fn + retry save
# ---------------------------------------------------------------------------

def test_loop_request_stop_commits_final_checkpoint(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    loop = FaultTolerantLoop(mgr, save_every=100, async_save=False)
    calls = []

    def step_fn(i, st):
        calls.append(i)
        return {"x": st["x"] + 1}

    def on_step(i, st, dt):
        if i == 3:
            loop.request_stop()

    step, state = loop.run(state={"x": np.zeros(())}, step_fn=step_fn,
                           n_steps=100, on_step=on_step,
                           extra_fn=lambda st, s: {"pos": s})
    assert step == 4 and calls == [0, 1, 2, 3]
    assert float(state["x"]) == 4.0
    got = mgr.restore_latest({"x": np.zeros(())})
    assert got is not None
    ck_step, _, extra = got
    assert ck_step == 4 and extra["pos"] == 4   # two-arg extra_fn got step


def test_loop_extra_fn_defaulted_second_param_stays_one_arg(tmp_path):
    """A defaulted second parameter keeps the one-arg contract — the step
    must not be misbound into it."""
    mgr = CheckpointManager(str(tmp_path), keep=3)
    loop = FaultTolerantLoop(mgr, save_every=100, async_save=False)
    step, _ = loop.run(state={"x": np.zeros(())},
                       step_fn=lambda i, st: st, n_steps=2,
                       extra_fn=lambda st, verbose=False: {"v": verbose})
    assert step == 2
    _, _, extra = mgr.restore_latest({"x": np.zeros(())})
    assert extra["v"] is False                  # not the step number


def test_loop_retry_exhausted_saves_at_failed_step(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    loop = FaultTolerantLoop(mgr, save_every=100, max_retries=2,
                             async_save=False)

    def step_fn(i, st):
        if i == 2:
            raise RuntimeError("persistent")
        return st

    saved = []
    with pytest.raises(RuntimeError, match="persistent"):
        loop.run(state={"x": np.zeros(())}, step_fn=step_fn, n_steps=10,
                 extra_fn=lambda st, s: saved.append(s) or {"pos": s})
    # the final save commits at the FAILED step (2), not past it
    assert saved[-1] == 2 and mgr.latest_step() == 2


# ---------------------------------------------------------------------------
# cluster shrinking: ClusterSpec, HostTopology, shrink_devices
# ---------------------------------------------------------------------------

def test_shrink_cluster_removes_and_drops_empty():
    spec = ClusterSpec(groups=(DeviceGroup("v100", V100_PAPER, 8),
                               DeviceGroup("t4", T4_16G, 4)))
    out = shrink_cluster(spec, {"v100": 4})
    assert [(g.name, g.n_devices) for g in out.groups] == [("v100", 4),
                                                           ("t4", 4)]
    out = shrink_cluster(spec, {"t4": 4})
    assert [(g.name, g.n_devices) for g in out.groups] == [("v100", 8)]
    with pytest.raises(ValueError, match="unknown device group"):
        shrink_cluster(spec, {"p100": 1})
    with pytest.raises(ValueError, match="cannot remove"):
        shrink_cluster(spec, {"t4": 5})
    with pytest.raises(ValueError, match="whole cluster"):
        shrink_cluster(spec, {"v100": 8, "t4": 4})


class _FakeDev:
    def __init__(self, i, proc=0):
        self.id = i
        self.process_index = proc


def test_shrink_devices_default_and_topology():
    devs = [_FakeDev(i, proc=i // 2) for i in range(6)]
    assert [d.id for d in shrink_devices(devs, {1})] == [0, 1, 4, 5]
    topo = HostTopology.uniform(3, 2, TPU_V5E)
    out = shrink_devices(devs, {0, 2}, topology=topo)
    assert [d.id for d in out] == [2, 3]
    # the deprecated callable form warns but still filters identically
    # (the mixed-fleet agreement regression lives in test_controller.py)
    with pytest.warns(DeprecationWarning, match="host_of"):
        legacy = shrink_devices(devs, {0, 2}, host_of=topo.host_of)
    assert [d.id for d in legacy] == [2, 3]


def test_host_topology_mapping_and_spec_merging():
    topo = HostTopology(hosts=(SimHost(0, V100_PAPER, 4),
                               SimHost(1, V100_PAPER, 4),
                               SimHost(2, T4_16G, 8)))
    assert topo.n_devices == 16
    assert topo.host_of(_FakeDev(0)) == 0
    assert topo.host_of(_FakeDev(7)) == 1
    assert topo.host_of(_FakeDev(8)) == 2
    with pytest.raises(ValueError):
        topo.host_of(_FakeDev(16))
    spec = topo.cluster_spec()
    # consecutive same-hardware hosts merge into one group
    assert [(g.hw.name, g.n_devices) for g in spec.groups] == [
        ("v100_eth35", 8), ("t4_16g", 8)]
    surv = topo.without({1})
    assert surv.host_ids == (0, 2)
    spec2 = surv.cluster_spec()
    assert [(g.hw.name, g.n_devices) for g in spec2.groups] == [
        ("v100_eth35", 4), ("t4_16g", 8)]
    assert not spec2.is_homogeneous
    devs = [_FakeDev(i) for i in range(16)]
    assert [d.id for d in topo.devices(devs, exclude={1})] == \
        list(range(4)) + list(range(8, 16))
    with pytest.raises(ValueError, match="every host"):
        topo.without({0, 1, 2})


def test_host_topology_eviction_keeps_survivor_devices():
    """Evicting a NON-last host must not slide survivors onto the evicted
    host's physical devices — offsets are preserved across without()."""
    topo = HostTopology.uniform(2, 2, TPU_V5E)
    surv = topo.without({0})
    devs = [_FakeDev(i) for i in range(4)]
    assert [d.id for d in surv.devices(devs)] == [2, 3]
    assert surv.host_of(_FakeDev(2)) == 1
    with pytest.raises(ValueError):
        surv.host_of(_FakeDev(0))          # evicted range is gone
    mid = HostTopology.uniform(3, 2, TPU_V5E).without({1})
    assert [d.id for d in mid.devices([_FakeDev(i) for i in range(6)])] \
        == [0, 1, 4, 5]


def test_host_topology_non_contiguous_hw_does_not_merge():
    topo = HostTopology(hosts=(SimHost(0, V100_PAPER, 2),
                               SimHost(1, P100_16G, 2),
                               SimHost(2, V100_PAPER, 2)))
    spec = topo.cluster_spec()
    assert [g.hw.name for g in spec.groups] == ["v100_eth35", "p100_16g",
                                                "v100_eth35"]
    assert {g.name for g in spec.groups} == {"v100_eth35#0", "p100_16g#1",
                                             "v100_eth35#2"}


# ---------------------------------------------------------------------------
# exactly-once data pipeline: mid-epoch restore + host-count invariance
# ---------------------------------------------------------------------------

def _hashes(pipe, n):
    return [pipe.next_batch()["tokens"].tobytes() for _ in range(n)]


def test_pipeline_exactly_once_mid_epoch_restore():
    """No repeated or skipped samples across a mid-epoch restore — the
    guarantee fault_tolerance.py's docstring claims."""
    cfg = DataCfg(global_batch=4, seq_len=8, vocab=101, seed=9,
                  steps_per_epoch=4)              # restore crosses an epoch
    reference = _hashes(TokenPipeline(cfg), 12)

    live = TokenPipeline(cfg)
    consumed = _hashes(live, 5)                   # 5 committed steps
    snapshot = live.state_dict()
    _hashes(live, 3)                              # lost post-ckpt work
    restored = TokenPipeline(cfg)
    restored.load_state_dict(snapshot)
    resumed = _hashes(restored, 7)
    assert consumed + resumed == reference        # exactly-once


def test_pipeline_content_invariant_to_host_count():
    """The global sample stream must not re-deal when the host count
    changes (straggler eviction re-shards the same global batch)."""
    cfg = DataCfg(global_batch=8, seq_len=16, vocab=997, seed=5)
    for step in range(3):
        full = TokenPipeline(cfg, host_id=0, n_hosts=1)
        for _ in range(step):
            full.next_batch()
        want = full.next_batch()["tokens"]
        shards = []
        for h in range(2):
            p = TokenPipeline(cfg, host_id=h, n_hosts=2)
            for _ in range(step):
                p.next_batch()
            shards.append(p.next_batch()["tokens"])
        np.testing.assert_array_equal(np.concatenate(shards), want)


def test_pipeline_reshard_continues_stream():
    cfg = DataCfg(global_batch=8, seq_len=16, vocab=997, seed=5)
    ref = _hashes(TokenPipeline(cfg, host_id=0, n_hosts=1), 6)
    p = TokenPipeline(cfg, host_id=0, n_hosts=2)
    for _ in range(3):
        p.next_batch()
    p1 = p.reshard(host_id=0, n_hosts=1)          # survivors re-divide
    assert _hashes(p1, 3) == ref[3:]              # position preserved


# ---------------------------------------------------------------------------
# eviction path: shrink_devices + remesh/rebalance onto survivors
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_evict_remesh_onto_surviving_devices(tmp_path):
    """Checkpoint on the full 2-host mesh, evict host 0 (the harder,
    non-last case), restore onto the survivors' devices — values
    identical, arrays actually live on the surviving half."""
    run_py(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.ckpt.checkpoint import CheckpointManager
        from repro.configs import get_config
        from repro.core.cost_model import TPU_V5E
        from repro.core.planner import compile_plan
        from repro.core.sharding import make_mesh
        from repro.models.lm import build, model_graph
        from repro.optim import adamw
        from repro.runtime.elastic import ElasticContext, HostTopology
        cfg = get_config("qwen3-1.7b", smoke=True)
        model = build(cfg)
        opt = adamw(lr=1e-3)
        topo = HostTopology.uniform(2, 2, TPU_V5E)
        mesh1 = make_mesh((4,), ("data",))
        plan1 = compile_plan(model, mesh1)
        with mesh1:
            params = plan1.init_params(jax.random.key(1))
            ost = opt.init(params)
        mgr = CheckpointManager({str(tmp_path)!r}, keep=2)
        mgr.save(5, {{"params": params, "opt": ost}},
                 extra={{"data": {{"epoch": 0, "step": 5, "seed": 0}}}})
        # --- evict host 0: survivors keep THEIR devices (2..3) ---
        surv = topo.without({{0}})
        devices = surv.devices(jax.devices())
        assert [d.id for d in devices] == [2, 3]
        ctx = ElasticContext(model=model, optimizer=opt)
        meta = model_graph(cfg, 8, 32).workload_meta()
        step, plan2, p2, o2, extra = ctx.rebalance(
            mgr, surv.cluster_spec(), meta, devices=devices,
            search_kw={{"max_pp": 1}})
        assert step == 5 and extra["data"]["step"] == 5
        assert set(d.id for d in plan2.mesh.devices.flat) == {{2, 3}}
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b))
        # restored leaves live only on the surviving devices
        for leaf in jax.tree.leaves(p2):
            assert set(d.id for d in leaf.sharding.device_set) <= {{2, 3}}
        batch = {{"tokens": jnp.zeros((4, 32), jnp.int32)}}
        with plan2.mesh:
            loss, _ = plan2.jit_loss(batch)(p2, batch)
        assert np.isfinite(float(loss))
        print("OK evict+rebalance restores onto survivors")
    """)


# ---------------------------------------------------------------------------
# end-to-end: the full self-healing loop under fault injection
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_self_healing_controller_end_to_end(tmp_path):
    """Acceptance scenario: a slow host is flagged and evicted, the job
    rebalances onto the survivors, resumes from the committed checkpoint
    with exactly-once data (including a transient crash retry), and the
    final loss matches an uninterrupted reference run on the same
    surviving cluster."""
    run_py(f"""
        import numpy as np
        from repro.ckpt.checkpoint import CheckpointManager
        from repro.configs import get_config
        from repro.core.cost_model import TPU_V5E
        from repro.data.pipeline import DataCfg, TokenPipeline
        from repro.launch.train import TrainController, ElasticConfig
        from repro.models.lm import build
        from repro.optim import adamw
        from repro.runtime.elastic import HostTopology
        from repro.runtime.faults import CrashStep, FaultInjector, SlowHost

        N = 12
        cfg = get_config("tinyllama-1.1b", smoke=True)
        model = build(cfg)

        class Recording(TokenPipeline):
            def __init__(self, *a, **k):
                super().__init__(*a, **k)
                self.seen = []
            def next_batch(self):
                b = super().next_batch()
                self.seen.append(b["tokens"].tobytes())
                return b

        dcfg = DataCfg(global_batch=8, seq_len=64, vocab=cfg.vocab, seed=0)

        # --- self-healing run: host 1 goes 5x slower at step 4, plus a
        #     transient crash at step 9 (retried on the SAME batch) ---
        data = Recording(dcfg)
        inj = FaultInjector(scenarios=(
            SlowHost(host=1, start_step=4, factor=5.0),
            CrashStep(step=9, times=1)), n_hosts=2, seed=0,
            nominal=0.05)    # simulated clock: immune to CI load spikes
        ctl = TrainController(
            model, cfg, adamw(lr=1e-3), data,
            CheckpointManager({str(tmp_path)!r} + "/heal", keep=3),
            elastic=ElasticConfig(
                topology=HostTopology.uniform(2, 2, TPU_V5E),
                patience=2, warmup=2),
            batch=8, seq=64, save_every=4, injector=inj, log_every=100)
        out = ctl.run(N, seed=0)
        assert out["phase"] == "DONE" and out["final_step"] == N, out["phase"]
        evicts = [e for e in out["events"] if e["kind"] == "evict"]
        rebs = [e for e in out["events"] if e["kind"] == "rebalance"]
        assert evicts and evicts[0]["hosts"] == [1], out["events"]
        assert rebs and rebs[0]["step"] == evicts[0]["step"], out["events"]
        assert out["topology"].host_ids == (0,)

        # --- exactly-once: the consumed global stream equals the
        #     reference stream, no repeats, no skips, crash included ---
        ref = TokenPipeline(dcfg)
        want = [ref.next_batch()["tokens"].tobytes() for _ in range(N)]
        assert data.seen == want, (len(data.seen), len(want))

        # --- uninterrupted reference on the surviving cluster ---
        data2 = Recording(dcfg)
        ctl2 = TrainController(
            model, cfg, adamw(lr=1e-3), data2,
            CheckpointManager({str(tmp_path)!r} + "/ref", keep=3),
            elastic=ElasticConfig(
                topology=HostTopology.uniform(1, 2, TPU_V5E)),
            batch=8, seq=64, save_every=100, log_every=100)
        out2 = ctl2.run(N, seed=0)
        assert out2["phase"] == "DONE"
        np.testing.assert_allclose(out["losses"][-1], out2["losses"][-1],
                                   rtol=2e-3)
        print("OK self-healing == uninterrupted reference:",
              out["losses"][-1], out2["losses"][-1])
    """)


@pytest.mark.slow
def test_preemption_checkpoint_and_resume(tmp_path):
    """SIGTERM mid-run commits a final checkpoint; a relaunched controller
    auto-resumes and the combined run consumes the stream exactly-once."""
    run_py(f"""
        from repro.ckpt.checkpoint import CheckpointManager
        from repro.configs import get_config
        from repro.core.cost_model import TPU_V5E
        from repro.data.pipeline import DataCfg, TokenPipeline
        from repro.launch.train import TrainController, ElasticConfig
        from repro.models.lm import build
        from repro.optim import adamw
        from repro.runtime.elastic import HostTopology
        from repro.runtime.faults import FaultInjector, Preemption

        N = 10
        cfg = get_config("tinyllama-1.1b", smoke=True)
        model = build(cfg)

        class Recording(TokenPipeline):
            seen = []
            def next_batch(self):
                b = super().next_batch()
                Recording.seen.append(b["tokens"].tobytes())
                return b

        dcfg = DataCfg(global_batch=4, seq_len=32, vocab=cfg.vocab, seed=1)

        def controller(injector=None):
            return TrainController(
                model, cfg, adamw(lr=1e-3), Recording(dcfg),
                CheckpointManager({str(tmp_path)!r}, keep=3),
                elastic=ElasticConfig(
                    topology=HostTopology.uniform(2, 1, TPU_V5E)),
                batch=4, seq=32, save_every=100, injector=injector,
                log_every=100)

        inj = FaultInjector(scenarios=(Preemption(step=5),), n_hosts=2,
                            nominal=0.05)
        out = controller(inj).run(N, seed=0)
        pre = [e for e in out["events"] if e["kind"] == "preempted"]
        assert pre and out["final_step"] == 6, out["events"]
        assert out["phase"] == "PREEMPTED", out["phase"]

        out2 = controller().run(N, seed=0)      # relaunch: auto-resume
        assert out2["final_step"] == N and out2["phase"] == "DONE"
        # steps 0..5 from run 1, 6..9 from run 2 — exactly once overall
        ref = TokenPipeline(dcfg)
        want = [ref.next_batch()["tokens"].tobytes() for _ in range(N)]
        assert Recording.seen == want, (len(Recording.seen), len(want))
        print("OK preempt at 6, resumed to", out2["final_step"])
    """)


# ---------------------------------------------------------------------------
# aggregator reset: the evicted set stays authoritative
# ---------------------------------------------------------------------------

def test_aggregator_reset_never_resurrects_evicted():
    """``reset(hosts)`` with a stale host list that still names an evicted
    host (e.g. a caller passing the pre-eviction ids) must not rebuild a
    monitor for it — an evicted host's heartbeats can keep arriving for a
    few steps and must never re-flag it."""
    agg = HostStragglerAggregator(n_hosts=3, threshold=2.0, patience=1,
                                  warmup=2)
    agg.evict(1)
    agg.reset([0, 1, 2])                    # 1 is evicted: must stay out
    assert set(agg.monitors) == {0, 2}
    for t in ({0: 1.0, 1: 1.0, 2: 1.0},) * 2:
        assert agg.observe(t) == []
    # a blatant outlier from the evicted host is silently ignored forever
    assert agg.observe({0: 1.0, 1: 50.0, 2: 1.0}) == []
    assert 1 not in agg.monitors and agg.evicted == {1}
    # default reset() (no host list) keeps the exclusion too
    agg.reset()
    assert set(agg.monitors) == {0, 2}


def test_aggregator_reset_after_rebalance_rearms_survivors():
    """Post-rebalance reset gives survivors *fresh* monitors (step times
    change shape under the new plan) while keeping eviction permanent."""
    agg = HostStragglerAggregator(n_hosts=2, threshold=2.0, patience=1,
                                  warmup=2)
    for t in ({0: 1.0, 1: 1.0},) * 2:
        agg.observe(t)
    assert agg.observe({0: 1.0, 1: 9.0}) == [1]
    agg.evict(1)
    agg.reset([0])
    assert agg.monitors[0].n == 0           # fresh stats, not carried over
    for t in ({0: 3.0},) * 2:               # new plan: slower baseline is OK
        assert agg.observe(t) == []
    assert agg.observe({0: 3.1}) == []


# ---------------------------------------------------------------------------
# kernel tiles across a hardware-mix-changing rebalance (stale-tiles fix)
# ---------------------------------------------------------------------------

def _tile_cfg():
    import dataclasses as dc

    from repro.configs import get_config
    return dc.replace(get_config("tinyllama-1.1b", smoke=True), n_layers=2,
                      attn_impl="pallas")


def test_plan_tiles_change_across_mix_changing_rebalance():
    """Re-planning after evicting the quarter-VMEM P100 group must re-run
    the autotuner: the conservative cross-group tiling gives way to the
    V100's larger blocks.  (A plan carrying the old tiles would run the
    survivors at the evicted part's geometry forever.)"""
    from repro.core.planner import compile_plan, mesh_for_strategy
    from repro.models.lm import build
    cfg = _tile_cfg()
    model = build(cfg)
    mixed = ClusterSpec(groups=(DeviceGroup("v100", V100_PAPER, 4),
                                DeviceGroup("p100", P100_16G, 4)))
    survivors = ClusterSpec(groups=(DeviceGroup("v100", V100_PAPER, 4),))
    mesh = mesh_for_strategy(StrategySpec(dp=1))
    before = compile_plan(model, mesh, cluster_spec=mixed)
    after = compile_plan(model, mesh, cluster_spec=survivors)
    assert before.tiles_for(None) != after.tiles_for(None)
    assert after.tiles_for(None).block_q > before.tiles_for(None).block_q
    assert set(after.kernel_tiles) == {"v100"}


def test_controller_retunes_baked_tiles_on_mix_change(tmp_path):
    """The regression the drift loop exposed: plans re-autotune, but the
    *executing model* bakes tile block sizes into its config at startup.
    ``_retune_model`` must re-size them when the hardware mix changes and
    emit a ``retune`` event."""
    from repro.launch.train import ElasticConfig, TrainController
    from repro.models.lm import build
    from repro.optim import adamw
    cfg = _tile_cfg()
    topo = HostTopology(hosts=(SimHost(0, V100_PAPER, 2),
                               SimHost(1, P100_16G, 2)))
    ctl = TrainController(
        build(cfg), cfg, adamw(lr=1e-3),
        TokenPipeline(DataCfg(global_batch=8, seq_len=64, vocab=cfg.vocab,
                              seed=0)),
        CheckpointManager(str(tmp_path / "tiles"), keep=1),
        elastic=ElasticConfig(topology=topo), batch=8, seq=64,
        verbose=False)
    ctl._retune_model(topo.cluster_spec())
    q_mixed = ctl.cfg.attn_block_q          # capped by the P100's 4 MiB VMEM
    ctl._retune_model(ClusterSpec(groups=(DeviceGroup("v100", V100_PAPER,
                                                      4),)))
    q_survivor = ctl.cfg.attn_block_q
    assert q_survivor > q_mixed, (q_mixed, q_survivor)
    assert any(e["kind"] == "retune" for e in ctl.events), ctl.events
    # the rebuilt model carries the new tiles (same parameter shapes)
    assert ctl.model.cfg.attn_block_q == q_survivor
