"""Event-driven cluster-membership runtime (DESIGN.md §12).

The machine layer (transition table, event folding/deferral, the merge
algebra of MembershipChange), the grow-side topology/cluster APIs
(with_host first-fit, grow_devices, grow_cluster), the injector's
one-shot membership playback and its topology grounding, and the
abort-without-commit loop discipline all run in-process.  The end-to-end
spot scenarios (drain within deadline → shed → re-admit → regrow;
deadline missed → fall back to the last committed checkpoint
exactly-once) run in subprocesses with virtual CPU devices.
"""
import itertools

import numpy as np
import pytest

from repro.ckpt.checkpoint import CheckpointManager
from repro.core.cost_model import (ClusterSpec, DeviceGroup, T4_16G,
                                   TPU_V5E, V100_PAPER)
from repro.core.hetero import grow_cluster, shrink_cluster
from repro.data.pipeline import DataCfg, TokenPipeline
from repro.runtime.controller import (DONE, DRAINING, FAILED, PREEMPTED,
                                      REBALANCING, RESUMING, RUNNING,
                                      TERMINAL, _TRANSITIONS, ClusterEvent,
                                      DriftSustained, HostJoin, HostLost,
                                      IllegalTransition, InjectorSource,
                                      MembershipChange,
                                      MembershipStateMachine,
                                      PreemptionWarning, StragglerSustained,
                                      change_for)
from repro.runtime.elastic import (HostTopology, SimHost, grow_devices,
                                   shrink_devices)
from repro.runtime.fault_tolerance import FaultTolerantLoop
from repro.runtime.faults import FaultInjector, JoinHost, SpotPreemption
from repro.runtime.straggler import HostStragglerAggregator

from subproc import run_py

ALL_STATES = (RUNNING, DRAINING, REBALANCING, RESUMING, DONE, PREEMPTED,
              FAILED)


def _events(step=3):
    """One instance of every concrete event type."""
    return (StragglerSustained(step=step, host=1, dt=0.4),
            DriftSustained(step=step, skew=1.5),
            PreemptionWarning(step=step, host=1, deadline_step=step + 2),
            HostLost(step=step, host=1),
            HostJoin(step=step, host=SimHost(7, TPU_V5E, 2)))


# ---------------------------------------------------------------------------
# state machine: the transition table is exhaustive and enforced
# ---------------------------------------------------------------------------

def test_transition_table_covers_every_state_pair():
    """to() permits exactly the table's edges — every other (from, to)
    pair raises IllegalTransition.  Exhaustive over all 7×7 pairs."""
    assert set(_TRANSITIONS) == set(ALL_STATES)
    for src, dst in itertools.product(ALL_STATES, ALL_STATES):
        m = MembershipStateMachine(state=src)
        if dst in _TRANSITIONS[src]:
            m.to(dst)
            assert m.state == dst
        else:
            with pytest.raises(IllegalTransition):
                m.to(dst)
            assert m.state == src          # a refused transition is a no-op


def test_terminal_states_have_no_exits():
    for t in TERMINAL:
        assert _TRANSITIONS[t] == frozenset()


def test_on_event_from_every_state_for_every_event_type():
    """RUNNING starts a drain, DRAINING folds in place, REBALANCING and
    RESUMING defer, terminal states raise — for all five event types."""
    for ev in _events():
        m = MembershipStateMachine()                       # RUNNING
        assert m.on_event(ev) is True
        assert m.state == DRAINING
        assert m.pending == change_for(ev)

        assert m.on_event(ev) is True                      # DRAINING: merge
        assert m.state == DRAINING
        assert m.pending == change_for(ev).merged(change_for(ev))
        assert m.deferred == ()

        for busy in (REBALANCING, RESUMING):
            b = MembershipStateMachine(state=busy)
            assert b.on_event(ev) is False                 # deferred, not
            assert b.pending.is_noop                       # folded
            assert b.deferred == (ev,)
            assert b.state == busy

        for t in TERMINAL:
            dead = MembershipStateMachine(state=t)
            with pytest.raises(IllegalTransition, match=t):
                dead.on_event(ev)


def test_take_and_take_deferred_clear():
    m = MembershipStateMachine()
    ev = StragglerSustained(step=2, host=0)
    m.on_event(ev)
    assert m.take() == change_for(ev)
    assert m.pending.is_noop                               # cleared
    m2 = MembershipStateMachine(state=REBALANCING)
    m2.on_event(ev)
    assert m2.take_deferred() == (ev,)
    assert m2.take_deferred() == ()                        # cleared


# ---------------------------------------------------------------------------
# change_for + the MembershipChange merge algebra
# ---------------------------------------------------------------------------

def test_change_for_every_event_type():
    s, d, w, l, j = _events(step=5)
    assert change_for(s) == MembershipChange(
        evict=(1,), reasons=("StragglerSustained",))
    assert change_for(d) == MembershipChange(
        recalibrate=1.5, reasons=("DriftSustained",))
    assert change_for(w) == MembershipChange(
        evict=(1,), deadline_step=7, reasons=("PreemptionWarning",))
    assert change_for(l) == MembershipChange(
        evict=(1,), abort=True, reasons=("HostLost",))
    assert change_for(j).admit == (j.host,)
    with pytest.raises(TypeError, match="not a ClusterEvent"):
        change_for(ClusterEvent(step=0))
    with pytest.raises(TypeError):
        change_for("straggler on host 1")


def test_membership_change_merge_semantics():
    a = MembershipChange(evict=(1, 2), deadline_step=9,
                         admit=(SimHost(5, TPU_V5E, 2),),
                         recalibrate=1.2, reasons=("A",))
    b = MembershipChange(evict=(2, 3), deadline_step=7, abort=True,
                         admit=(SimHost(5, TPU_V5E, 4),
                                SimHost(6, TPU_V5E, 2)),
                         recalibrate=1.5, reasons=("B",))
    m = a.merged(b)
    assert m.evict == (1, 2, 3)             # dedupe-union, order preserved
    # admit dedupes by host id — first sighting wins (5 keeps 2 devices)
    assert [(h.host, h.n_devices) for h in m.admit] == [(5, 2), (6, 2)]
    assert m.recalibrate == 1.5             # max skew
    assert m.abort is True                  # sticky OR
    assert m.deadline_step == 7             # earliest deadline binds
    assert m.reasons == ("A", "B")
    # abort and deadline survive a merge with an empty change, both ways
    assert MembershipChange().merged(m).abort is True
    assert m.merged(MembershipChange()).deadline_step == 7
    assert MembershipChange().is_noop
    assert MembershipChange(abort=True).is_noop  # abort alone reshapes nothing
    assert not MembershipChange(evict=(1,)).is_noop


# ---------------------------------------------------------------------------
# grow-side topology: with_host first-fit + grow_devices
# ---------------------------------------------------------------------------

class _FakeDev:
    def __init__(self, i):
        self.id = i
        self.process_index = 0


def test_with_host_first_fit_reclaims_evicted_range():
    """A re-admitted host lands in the gap the eviction vacated — the
    flat device list never grows just because membership churned."""
    topo = HostTopology.uniform(3, 2, TPU_V5E)             # [0,2) [2,4) [4,6)
    surv = topo.without({1})                               # gap at [2,4)
    back = surv.with_host(SimHost(9, TPU_V5E, 2))
    assert {(h.host, h.offset) for h in back.hosts} == {
        (0, 0), (9, 2), (2, 4)}
    assert back.n_devices == 6
    devs = [_FakeDev(i) for i in range(6)]
    assert [d.id for d in back.devices(devs)] == [0, 1, 2, 3, 4, 5]
    # too big for the gap → appended past the tail
    wide = surv.with_host(SimHost(9, TPU_V5E, 3))
    assert {(h.host, h.offset) for h in wide.hosts} == {
        (0, 0), (2, 4), (9, 6)}


def test_with_host_loud_errors():
    topo = HostTopology.uniform(2, 2, TPU_V5E)
    with pytest.raises(ValueError, match="already a member"):
        topo.with_host(SimHost(1, TPU_V5E, 2))
    with pytest.raises(ValueError, match="at least one device"):
        topo.with_host(SimHost(5, TPU_V5E, 0))
    with pytest.raises(ValueError, match="overlapping"):
        topo.with_host(SimHost(5, TPU_V5E, 2, offset=1))
    # an explicit non-overlapping offset is honoured verbatim
    parked = topo.with_host(SimHost(5, TPU_V5E, 2, offset=10))
    assert {(h.host, h.offset) for h in parked.hosts} == {
        (0, 0), (1, 2), (5, 10)}


def test_grow_devices_round_trips_shrink():
    """Shed a mid-fleet host, re-admit it: the device list is restored
    (grow is the inverse of shrink, down to physical device identity)."""
    topo = HostTopology.uniform(3, 2, TPU_V5E)
    devs = [_FakeDev(i) for i in range(6)]
    before = [d.id for d in topo.devices(devs)]
    surv = topo.without({1})
    assert [d.id for d in shrink_devices(devs, {1}, topology=topo)] \
        == [d.id for d in surv.devices(devs)] == [0, 1, 4, 5]
    regrown_devs, regrown = grow_devices(
        devs, [SimHost(1, TPU_V5E, 2)], topology=surv)
    assert [d.id for d in regrown_devs] == before
    assert regrown.host_ids == (0, 1, 2)
    assert regrown.cluster_spec() == topo.cluster_spec()


# ---------------------------------------------------------------------------
# grow_cluster: group-keyed admission, inverse of shrink_cluster
# ---------------------------------------------------------------------------

def test_grow_cluster_adds_and_appends():
    spec = ClusterSpec(groups=(DeviceGroup("v100", V100_PAPER, 4),
                               DeviceGroup("t4", T4_16G, 4)))
    out = grow_cluster(spec, {"v100": 4})
    assert [(g.name, g.n_devices) for g in out.groups] == [("v100", 8),
                                                           ("t4", 4)]
    out = grow_cluster(spec, {}, new_groups=(
        DeviceGroup("tpu", TPU_V5E, 8),))
    assert [(g.name, g.n_devices) for g in out.groups] == [
        ("v100", 4), ("t4", 4), ("tpu", 8)]


def test_grow_cluster_loud_errors():
    spec = ClusterSpec(groups=(DeviceGroup("v100", V100_PAPER, 4),))
    with pytest.raises(ValueError, match="unknown device group"):
        grow_cluster(spec, {"t4": 2})
    with pytest.raises(ValueError, match="at least one device"):
        grow_cluster(spec, {"v100": 0})
    with pytest.raises(ValueError, match="collides"):
        grow_cluster(spec, {}, new_groups=(
            DeviceGroup("v100", V100_PAPER, 2),))
    with pytest.raises(ValueError, match="n_devices=0"):
        grow_cluster(spec, {}, new_groups=(DeviceGroup("t4", T4_16G, 0),))


def test_grow_cluster_inverts_shrink_cluster():
    spec = ClusterSpec(groups=(DeviceGroup("v100", V100_PAPER, 8),
                               DeviceGroup("t4", T4_16G, 4)))
    assert grow_cluster(shrink_cluster(spec, {"v100": 4}),
                        {"v100": 4}) == spec
    # a group shrunk to nothing comes back via new_groups
    gone = shrink_cluster(spec, {"t4": 4})
    assert grow_cluster(gone, {}, new_groups=(
        DeviceGroup("t4", T4_16G, 4),)) == spec


# ---------------------------------------------------------------------------
# shrink_devices: host-keyed unification (the deprecated callable form)
# ---------------------------------------------------------------------------

def test_shrink_devices_host_of_deprecated_but_agrees():
    """Mixed V100/T4 fleet: the deprecated ``host_of=`` callable form
    warns, and selects the identical survivors as the host-keyed
    ``topology=`` form and ``HostTopology.without``."""
    topo = HostTopology(hosts=(SimHost(0, V100_PAPER, 2),
                               SimHost(1, T4_16G, 4),
                               SimHost(2, V100_PAPER, 2)))
    devs = [_FakeDev(i) for i in range(topo.n_devices)]
    want = [d.id for d in shrink_devices(devs, {1}, topology=topo)]
    with pytest.warns(DeprecationWarning, match="host_of"):
        legacy = shrink_devices(devs, {1}, host_of=topo.host_of)
    assert [d.id for d in legacy] == want == [0, 1, 6, 7]
    assert [d.id for d in topo.without({1}).devices(devs)] == want


# ---------------------------------------------------------------------------
# data stream: growing the host count keeps the global stream invariant
# ---------------------------------------------------------------------------

def test_pipeline_reshard_up_keeps_global_stream():
    """Growing 1 → 2 hosts mid-stream: the concatenation of the new
    shards continues the exact global stream (the shrink-direction twin
    of test_pipeline_reshard_continues_stream)."""
    cfg = DataCfg(global_batch=8, seq_len=16, vocab=997, seed=5)
    full = TokenPipeline(cfg, host_id=0, n_hosts=1)
    ref = [full.next_batch()["tokens"] for _ in range(6)]
    p = TokenPipeline(cfg, host_id=0, n_hosts=1)
    for _ in range(3):
        p.next_batch()
    shards = [p.reshard(host_id=h, n_hosts=2) for h in range(2)]
    for step in range(3, 6):
        got = np.concatenate([s.next_batch()["tokens"] for s in shards])
        np.testing.assert_array_equal(got, ref[step])


# ---------------------------------------------------------------------------
# injector membership playback + InjectorSource topology grounding
# ---------------------------------------------------------------------------

def test_injector_membership_one_shot_and_late_delivery():
    inj = FaultInjector(scenarios=(
        SpotPreemption(host=1, warn_step=5, deadline_steps=2),
        JoinHost(host=2, step=3, n_devices=2)), n_hosts=2)
    assert inj.membership(2) == []
    # step 3 and 5 fell inside a (hypothetical) rebalance window: the
    # signals still deliver at the next polled step, each exactly once
    got = inj.membership(6)
    assert [(k, type(s).__name__) for k, s in got] == [
        ("preempt_warn", "SpotPreemption"), ("join", "JoinHost")]
    assert [k for k, _ in inj.membership(7)] == ["host_lost"]
    assert inj.membership(8) == [] and inj.membership(100) == []


def test_injector_zero_deadline_warn_and_lost_same_step():
    inj = FaultInjector(scenarios=(
        SpotPreemption(host=0, warn_step=4, deadline_steps=0),))
    assert [k for k, _ in inj.membership(4)] == ["preempt_warn",
                                                 "host_lost"]


def test_injector_source_grounds_events_against_live_topology():
    topo = HostTopology.uniform(2, 2, TPU_V5E)             # hosts 0, 1
    inj = FaultInjector(scenarios=(
        SpotPreemption(host=7, warn_step=1, deadline_steps=1),  # not ours
        SpotPreemption(host=1, warn_step=2, deadline_steps=2),
        JoinHost(host=0, step=2, n_devices=2),             # already present
        JoinHost(host=3, step=2, n_devices=2, hw=None)))   # hw defaulted
    src = InjectorSource(inj, default_hw=T4_16G)
    # the foreign host's warn/lost are consumed but emit nothing
    assert src.poll(1, {}, topo) == []
    evs = src.poll(2, {}, topo)
    kinds = {type(e).__name__ for e in evs}
    assert kinds == {"PreemptionWarning", "HostJoin"}
    warn = next(e for e in evs if isinstance(e, PreemptionWarning))
    assert warn.host == 1 and warn.deadline_step == 4
    join = next(e for e in evs if isinstance(e, HostJoin))
    assert (join.host.host, join.host.hw, join.host.n_devices) \
        == (3, T4_16G, 2)
    # after the shed, the host-lost for an already-absent host is dropped
    shed = topo.without({1})
    assert src.poll(4, {}, shed) == []


# ---------------------------------------------------------------------------
# aggregator: admission is the one way back in
# ---------------------------------------------------------------------------

def test_aggregator_admit_reverses_eviction():
    agg = HostStragglerAggregator(n_hosts=2, threshold=2.0, patience=1,
                                  warmup=2)
    agg.evict(1)
    assert agg.observe({0: 1.0, 1: 50.0}) == []            # ignored
    agg.admit(1)
    assert 1 in agg.monitors and agg.evicted == set()
    agg.reset([0, 1])                                      # no resurrection
    assert set(agg.monitors) == {0, 1}                     # needed: admitted
    for t in ({0: 1.0, 1: 1.0},) * 2:
        assert agg.observe(t) == []
    # a re-admitted host is watched like any other — it can re-flag
    assert agg.observe({0: 1.0, 1: 50.0}) == [1]


# ---------------------------------------------------------------------------
# abort: the drain-failed path commits NOTHING
# ---------------------------------------------------------------------------

def test_loop_request_abort_commits_nothing_past_last_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    loop = FaultTolerantLoop(mgr, save_every=4, async_save=False)

    def on_step(i, st, dt):
        if i == 5:
            loop.request_abort()

    step, state = loop.run(state={"x": np.zeros(())},
                           step_fn=lambda i, st: {"x": st["x"] + 1},
                           n_steps=100, on_step=on_step,
                           extra_fn=lambda st, s: {"pos": s})
    assert step == 6 and loop.aborted
    # the periodic save at 4 is the last commit — no final save at 6
    assert mgr.latest_step() == 4
    _, tree, extra = mgr.restore_latest({"x": np.zeros(())})
    assert float(tree["x"]) == 4.0 and extra["pos"] == 4
    # a normal run re-arms the flag
    step, _ = loop.run(state=state, step_fn=lambda i, st: st, n_steps=8,
                       start_step=step)
    assert step == 8 and not loop.aborted


# ---------------------------------------------------------------------------
# controller guards: the one apply path refuses to run out of phase
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_apply_membership_change_guards(tmp_path):
    from repro.configs import get_config
    from repro.models.lm import build
    from repro.optim import adamw
    from repro.runtime.controller import ClusterController, ElasticConfig
    cfg = get_config("tinyllama-1.1b", smoke=True)
    ctl = ClusterController(
        build(cfg), cfg, adamw(lr=1e-3),
        TokenPipeline(DataCfg(global_batch=8, seq_len=32, vocab=cfg.vocab,
                              seed=0)),
        CheckpointManager(str(tmp_path), keep=1),
        elastic=ElasticConfig(topology=HostTopology.uniform(2, 1, TPU_V5E)),
        batch=8, seq=32, verbose=False)
    assert ctl.phase == RUNNING
    with pytest.raises(IllegalTransition, match="outside REBALANCING"):
        ctl.apply_membership_change(MembershipChange(evict=(1,)), at_step=0)
    ctl.machine.state = REBALANCING
    with pytest.raises(ValueError, match="no-op"):
        ctl.apply_membership_change(MembershipChange(abort=True), at_step=0)


# ---------------------------------------------------------------------------
# end-to-end: spot drain → shed → re-admit → regrow
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_spot_drain_and_regrow_end_to_end(tmp_path):
    """Acceptance scenario: a spot notice drains host 1 within its
    deadline, the job rebalances onto the survivor, the host's capacity
    re-joins later, and the regrown plan's predicted step cost matches a
    never-preempted fleet's to within 5% (here: identical spec, so
    identical prediction).  The data stream is consumed exactly-once
    throughout — both membership changes committed their drains."""
    run_py(f"""
        from repro.ckpt.checkpoint import CheckpointManager
        from repro.configs import get_config
        from repro.core.cost_model import TPU_V5E
        from repro.data.pipeline import DataCfg, TokenPipeline
        from repro.models.lm import build, model_graph
        from repro.optim import adamw
        from repro.runtime.controller import ClusterController, ElasticConfig
        from repro.runtime.elastic import HostTopology
        from repro.runtime.elastic import search_cluster
        from repro.runtime.faults import (FaultInjector, JoinHost,
                                          SpotPreemption)

        N = 24
        cfg = get_config("tinyllama-1.1b", smoke=True)
        model = build(cfg)

        class Recording(TokenPipeline):
            seen = []
            def next_batch(self):
                b = super().next_batch()
                Recording.seen.append(b["tokens"].tobytes())
                return b

        dcfg = DataCfg(global_batch=8, seq_len=32, vocab=cfg.vocab, seed=3)
        topo = HostTopology.uniform(2, 2, TPU_V5E)
        inj = FaultInjector(scenarios=(
            SpotPreemption(host=1, warn_step=6, deadline_steps=2),
            JoinHost(host=2, step=14, n_devices=2)), n_hosts=2,
            nominal=0.05)
        ctl = ClusterController(
            model, cfg, adamw(lr=1e-3), Recording(dcfg),
            CheckpointManager({str(tmp_path)!r}, keep=3),
            elastic=ElasticConfig(topology=topo, max_rebalances=4),
            batch=8, seq=32, save_every=4, injector=inj, log_every=100)
        out = ctl.run(N, seed=0)
        assert out["phase"] == "DONE" and out["final_step"] == N, out
        kinds = [e["kind"] for e in out["events"]]
        warns = [e for e in out["events"] if e["kind"] == "preempt_warn"]
        evicts = [e for e in out["events"] if e["kind"] == "evict"]
        joins = [e for e in out["events"] if e["kind"] == "join"]
        rebs = [e for e in out["events"] if e["kind"] == "rebalance"]
        assert warns and warns[0]["host"] == 1 \
            and warns[0]["deadline_step"] == 8, out["events"]
        # the drain beat the deadline: shed at or before step 8, no abort
        assert evicts and evicts[0]["hosts"] == [1] \
            and evicts[0]["step"] <= 8, out["events"]
        assert "host_lost" not in kinds, out["events"]
        assert joins and joins[0]["hosts"] == [2] \
            and joins[0]["total_devices"] == 4, out["events"]
        assert len(rebs) == 2, out["events"]
        # shed then regrown: back to 2 hosts x 2 devices
        assert out["topology"].host_ids == (0, 2)
        assert out["topology"].n_devices == 4

        # post-grow plan within 5% of the never-preempted plan's predicted
        # cost (ISSUE acceptance: re-admission restores full capacity)
        meta = model_graph(cfg, 8, 32).workload_meta()
        kw = {{"max_pp": 1}}
        t_grown = search_cluster(meta, out["topology"].cluster_spec(),
                                 search_kw=kw).total
        t_never = search_cluster(meta, topo.cluster_spec(),
                                 search_kw=kw).total
        assert abs(t_grown / t_never - 1.0) <= 0.05, (t_grown, t_never)

        # exactly-once: both drains committed, so no batch repeated/skipped
        ref = TokenPipeline(dcfg)
        want = [ref.next_batch()["tokens"].tobytes() for _ in range(N)]
        assert Recording.seen == want, (len(Recording.seen), len(want))
        print("OK spot drain+regrow:", kinds)
    """)


@pytest.mark.slow
def test_spot_deadline_missed_falls_back_exactly_once(tmp_path):
    """deadline_steps=0 models a missed notice: warn and loss land on the
    same step, no drain checkpoint can commit, and the controller must
    restore the last *committed* checkpoint and replay the lost steps on
    the survivors — each replayed step re-draws its original batch."""
    run_py(f"""
        from repro.ckpt.checkpoint import CheckpointManager
        from repro.configs import get_config
        from repro.core.cost_model import TPU_V5E
        from repro.data.pipeline import DataCfg, TokenPipeline
        from repro.models.lm import build
        from repro.optim import adamw
        from repro.runtime.controller import ClusterController, ElasticConfig
        from repro.runtime.elastic import HostTopology
        from repro.runtime.faults import FaultInjector, SpotPreemption

        N = 12
        SAVE = 4
        cfg = get_config("tinyllama-1.1b", smoke=True)
        model = build(cfg)

        class Recording(TokenPipeline):
            seen = []
            def next_batch(self):
                b = super().next_batch()
                Recording.seen.append(b["tokens"].tobytes())
                return b

        dcfg = DataCfg(global_batch=8, seq_len=32, vocab=cfg.vocab, seed=4)
        inj = FaultInjector(scenarios=(
            SpotPreemption(host=1, warn_step=6, deadline_steps=0),),
            n_hosts=2, nominal=0.05)
        ctl = ClusterController(
            model, cfg, adamw(lr=1e-3), Recording(dcfg),
            CheckpointManager({str(tmp_path)!r}, keep=3),
            elastic=ElasticConfig(topology=HostTopology.uniform(2, 2,
                                                               TPU_V5E)),
            batch=8, seq=32, save_every=SAVE, injector=inj, log_every=100)
        out = ctl.run(N, seed=0)
        assert out["phase"] == "DONE" and out["final_step"] == N, out
        lost = [e for e in out["events"] if e["kind"] == "host_lost"]
        evicts = [e for e in out["events"] if e["kind"] == "evict"]
        rebs = [e for e in out["events"] if e["kind"] == "rebalance"]
        assert lost and lost[0]["host"] == 1, out["events"]
        assert evicts and evicts[0]["hosts"] == [1], out["events"]
        # the abort threw away the uncommitted tail: the rebalance resumed
        # from the last periodic checkpoint, not from the abort step
        assert rebs and rebs[0]["step"] == SAVE, out["events"]
        assert out["topology"].host_ids == (0,)

        # exactly-once under replay: the run drew batches 0..6 (abort hit
        # after step 6 ran), fell back to step 4, then replayed 4..N-1
        # with byte-identical content — the committed trajectory saw each
        # batch exactly once
        lost_at = lost[0]["step"]
        ref = TokenPipeline(dcfg)
        want = [ref.next_batch()["tokens"].tobytes() for _ in range(N)]
        seen = Recording.seen
        assert seen == want[:lost_at + 1] + want[SAVE:], \
            (lost_at, len(seen), len(want))
        print("OK deadline missed: lost at", lost_at, "resumed at", SAVE)
    """)


@pytest.mark.slow
def test_pure_scale_up_join_end_to_end(tmp_path):
    """No failure at all: a host simply offers capacity mid-run and the
    controller grows onto it — the symmetric half of the evict loop."""
    run_py(f"""
        from repro.ckpt.checkpoint import CheckpointManager
        from repro.configs import get_config
        from repro.core.cost_model import TPU_V5E
        from repro.data.pipeline import DataCfg, TokenPipeline
        from repro.models.lm import build
        from repro.optim import adamw
        from repro.runtime.controller import ClusterController, ElasticConfig
        from repro.runtime.elastic import HostTopology
        from repro.runtime.faults import FaultInjector, JoinHost

        N = 12
        cfg = get_config("tinyllama-1.1b", smoke=True)
        model = build(cfg)
        dcfg = DataCfg(global_batch=8, seq_len=32, vocab=cfg.vocab, seed=6)
        inj = FaultInjector(scenarios=(JoinHost(host=1, step=5,
                                                n_devices=2),),
                            n_hosts=1, nominal=0.05)
        ctl = ClusterController(
            model, cfg, adamw(lr=1e-3), TokenPipeline(dcfg),
            CheckpointManager({str(tmp_path)!r}, keep=3),
            elastic=ElasticConfig(topology=HostTopology.uniform(1, 2,
                                                               TPU_V5E)),
            batch=8, seq=32, save_every=4, injector=inj, log_every=100)
        out = ctl.run(N, seed=0)
        assert out["phase"] == "DONE" and out["final_step"] == N, out
        joins = [e for e in out["events"] if e["kind"] == "join"]
        assert joins and joins[0]["hosts"] == [1], out["events"]
        assert out["topology"].host_ids == (0, 1)
        assert out["topology"].n_devices == 4
        assert not any(e["kind"] == "evict" for e in out["events"])
        print("OK scale-up join at step", joins[0]["step"])
    """)
