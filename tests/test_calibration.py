"""Profile-calibrated cost model (DESIGN.md §10).

Layers under test:
  (a) the linear feature decomposition — ``predict_step_time`` over
      ``step_cost_features`` must equal ``step_cost``'s analytic
      compute+comm+bubble *exactly*, for every strategy shape (this
      identity is what makes calibration a linear least-squares problem);
  (b) the round-trip property — ``fit`` over observations synthesized
      from a ground-truth table recovers its rates (noise-free to ridge
      precision, 5%-jittered to well inside 10%), and the fitted
      ``CalibratedHardware`` is a drop-in ``Hardware`` everywhere;
  (c) the profiler plumbing — ring-effective byte accounting, sliding
      windows, per-group fits over a ``ClusterSpec``;
  (d) the drift loop — ``DriftHost`` ramps, and the end-to-end
      controller detects sustained predicted-vs-measured skew, re-fits,
      and resumes (subprocess, simulated clock).
"""
import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.calibrate import (CalibratedHardware, Observation, fit,
                                  parameter_error, prediction_error,
                                  refit_spec, synthesize_observations)
from repro.core.cost_model import (CALIBRATION_PARAMS, ClusterSpec,
                                   DeviceGroup, Hardware, StrategySpec,
                                   T4_16G, TPU_V5E, V100_PAPER,
                                   hardware_reciprocals, predict_step_time, step_cost,
                                   step_cost_features)
from repro.core.hetero import plan_placement, price_batch_shares
from repro.models.lm import model_graph
from repro.runtime.faults import DriftHost, FaultInjector
from repro.runtime.profiler import Profiler, ring_effective_bytes

from subproc import run_py


def _meta(batch=256, seq=512, arch="tinyllama-1.1b"):
    from repro.configs import get_config
    return model_graph(get_config(arch), batch, seq).workload_meta()


# ---------------------------------------------------------------------------
# (a) the linear identity: features · reciprocals == analytic step cost
# ---------------------------------------------------------------------------

STRATS = [
    StrategySpec(dp=8),
    StrategySpec(dp=4, tp=2),
    StrategySpec(dp=2, tp=2, pp=2, micro_batches=4),
    StrategySpec(dp=1, tp=4, pp=2, micro_batches=8, schedule="1f1b"),
    StrategySpec(dp=8, zero=3),
    StrategySpec(dp=4, tp=2, vocab_split=False),
]


@pytest.mark.parametrize("hw", [V100_PAPER, T4_16G, TPU_V5E],
                         ids=lambda h: h.name)
@pytest.mark.parametrize("strat", STRATS, ids=lambda s: s.describe())
@pytest.mark.parametrize("overlap", [0.0, 0.5])
def test_features_reproduce_step_cost(hw, strat, overlap):
    """``predict_step_time(step_cost_features(...))`` equals the analytic
    compute + comm + bubble to float precision.  (``total`` also folds in
    memory *feasibility* — infinite when the plan OOMs — which is
    orthogonal to the timing decomposition, so the identity is checked
    against the three timed terms.)"""
    meta = _meta()
    cb = step_cost(meta, strat, hw, overlap=overlap)
    feats = step_cost_features(meta, strat, hw, overlap=overlap)
    want = cb.compute + cb.comm + cb.bubble
    got = predict_step_time(feats, hw)
    assert got == pytest.approx(want, rel=1e-9), (strat.describe(), cb)


def test_features_reproduce_step_cost_moe():
    from repro.configs import get_config
    meta = model_graph(get_config("deepseek-moe-16b"), 64, 512).workload_meta()
    for strat in (StrategySpec(dp=8, ep=4), StrategySpec(dp=4, tp=2, ep=2),
                  StrategySpec(dp=8, ep=8, zero=3)):
        cb = step_cost(meta, strat, V100_PAPER, overlap=0.5)
        feats = step_cost_features(meta, strat, V100_PAPER, overlap=0.5)
        want = cb.compute + cb.comm + cb.bubble
        assert predict_step_time(feats, V100_PAPER) == pytest.approx(
            want, rel=1e-9), strat.describe()


def test_features_cover_only_calibration_params():
    feats = step_cost_features(_meta(), StrategySpec(dp=4, tp=2),
                               V100_PAPER)
    assert set(feats) == set(CALIBRATION_PARAMS)
    assert all(v >= 0.0 for v in feats.values())
    assert feats["eff_flops"] > 0.0


# ---------------------------------------------------------------------------
# (b) round trip: fit recovers a ground-truth table
# ---------------------------------------------------------------------------

TRUTH = dataclasses.replace(
    V100_PAPER, peak_flops=V100_PAPER.peak_flops * 0.7,
    hbm_bw=V100_PAPER.hbm_bw * 1.35,
    link_bw={"fast": V100_PAPER.link_bw["fast"] * 0.8,
             "slow": V100_PAPER.link_bw["slow"] * 1.3})


def test_fit_recovers_truth_noise_free():
    obs = synthesize_observations(_meta(), StrategySpec(dp=4, tp=2), TRUTH,
                                  n_steps=16)
    fitted = fit(obs, V100_PAPER)
    assert parameter_error(fitted, TRUTH) < 1e-3   # ridge bias only
    assert prediction_error(obs, fitted) < 1e-3
    assert all(fitted.confidence[p] > 0.8 for p in CALIBRATION_PARAMS), \
        fitted.confidence


def test_fit_recovers_truth_under_noise():
    obs = synthesize_observations(_meta(), StrategySpec(dp=4, tp=2), TRUTH,
                                  n_steps=200, noise=0.05, seed=7)
    fitted = fit(obs, V100_PAPER)
    err, prior_err = (parameter_error(fitted, TRUTH),
                      parameter_error(V100_PAPER, TRUTH))
    assert err < 0.10, err                          # the acceptance gate
    assert err < prior_err / 4, (err, prior_err)    # and a real improvement


@settings(max_examples=15, deadline=None)
@given(scales=st.tuples(*([st.floats(0.4, 2.5)] * 4)))
def test_fit_round_trip_property(scales):
    """Any physically-plausible perturbation of every rate entry is
    recovered from noise-free decomposed observations."""
    sf, sh, sl_f, sl_s = scales
    truth = dataclasses.replace(
        V100_PAPER, peak_flops=V100_PAPER.peak_flops * sf,
        hbm_bw=V100_PAPER.hbm_bw * sh,
        link_bw={"fast": V100_PAPER.link_bw["fast"] * sl_f,
                 "slow": V100_PAPER.link_bw["slow"] * sl_s})
    obs = synthesize_observations(_meta(batch=64), StrategySpec(dp=4, tp=2),
                                  truth, n_steps=8)
    assert parameter_error(fit(obs, V100_PAPER), truth) < 1e-2


def test_compute_only_observations_keep_links_at_prior():
    """Unobserved parameters are not hallucinated: they stay exactly at
    the prior with zero confidence."""
    obs = [o for o in synthesize_observations(
        _meta(), StrategySpec(dp=4, tp=2), TRUTH, n_steps=16)
        if o.kind == "compute"]
    fitted = fit(obs, V100_PAPER)
    r_fit, r_prior = (hardware_reciprocals(fitted),
                      hardware_reciprocals(V100_PAPER))
    for p in ("link_fast", "link_slow", "hbm_bw"):
        assert r_fit[p] == pytest.approx(r_prior[p])
        assert fitted.confidence[p] == 0.0
    assert parameter_error(fitted, TRUTH, params=("eff_flops",)) < 1e-3
    assert fitted.confidence["eff_flops"] > 0.8


def test_whole_step_observations_still_predict_well():
    """Whole-step times are collinear (one row shape), so per-parameter
    recovery is not identifiable — but the ridge-to-prior fit must still
    *predict* step times accurately."""
    obs = synthesize_observations(_meta(), StrategySpec(dp=4, tp=2), TRUTH,
                                  n_steps=32, decomposed=False)
    fitted = fit(obs, V100_PAPER)
    assert prediction_error(obs, fitted) < 0.05
    # and the prior is much worse on the same observations
    assert prediction_error(obs, V100_PAPER) > 3 * prediction_error(
        obs, fitted)


def test_fit_without_observations_returns_prior():
    fitted = fit([], V100_PAPER)
    assert parameter_error(fitted, V100_PAPER) == 0.0
    assert fitted.n_observations == 0
    assert all(v == 0.0 for v in fitted.confidence.values())
    assert fitted.base_name == V100_PAPER.name


def test_confidence_discounts_small_samples():
    few = fit(synthesize_observations(_meta(), StrategySpec(dp=4, tp=2),
                                      TRUTH, n_steps=2, noise=0.05, seed=1),
              V100_PAPER)
    many = fit(synthesize_observations(_meta(), StrategySpec(dp=4, tp=2),
                                       TRUTH, n_steps=64, noise=0.05,
                                       seed=1),
               V100_PAPER)
    assert few.confidence["eff_flops"] < many.confidence["eff_flops"]


def test_calibrated_hardware_is_drop_in():
    """A fitted table flows through every ``Hardware`` consumer: cost
    model, hetero balancer, strategy search, kernel autotuner."""
    from repro.core.auto import search
    from repro.kernels.autotune import autotune
    obs = synthesize_observations(_meta(), StrategySpec(dp=4, tp=2), TRUTH,
                                  n_steps=16)
    fitted = fit(obs, V100_PAPER)
    assert isinstance(fitted, Hardware)
    meta = _meta()
    cb = step_cost(meta, StrategySpec(dp=4, tp=2), fitted)
    want = step_cost(meta, StrategySpec(dp=4, tp=2), TRUTH)
    assert cb.total == pytest.approx(want.total, rel=1e-3)
    spec = ClusterSpec(groups=(DeviceGroup("fit", fitted, 8),
                               DeviceGroup("t4", T4_16G, 8)))
    pl = plan_placement(meta, StrategySpec(dp=8, tp=2), spec, overlap=0.5)
    assert sum(pl.batch_shares) == meta.batch
    assert search(meta, spec, top_k=1, overlap=0.5, max_pp=1)
    tiles = autotune(fitted, head_dim=128, group=4, d_model=2048,
                     vocab=32000)
    assert tiles == autotune(V100_PAPER, head_dim=128, group=4,
                             d_model=2048, vocab=32000), \
        "vmem/hbm capacity unchanged → same tile geometry"


def test_refit_spec_is_partial_and_name_keyed():
    spec = ClusterSpec(groups=(DeviceGroup("a", V100_PAPER, 8),
                               DeviceGroup("b", T4_16G, 8)))
    fitted = fit(synthesize_observations(
        _meta(), StrategySpec(dp=4, tp=2), TRUTH, n_steps=8), V100_PAPER)
    out = refit_spec(spec, {"a": fitted})
    assert out.groups[0].hw is fitted
    assert out.groups[1].hw is T4_16G          # no observations → prior
    assert [g.name for g in out.groups] == ["a", "b"]


def test_fit_chains_base_name_through_refits():
    obs = synthesize_observations(_meta(), StrategySpec(dp=4, tp=2), TRUTH,
                                  n_steps=8)
    first = fit(obs, V100_PAPER)
    second = fit(obs, first)                   # recalibrate the calibrated
    assert isinstance(second, CalibratedHardware)
    assert first.base_name == V100_PAPER.name
    assert second.base_name == V100_PAPER.name


# ---------------------------------------------------------------------------
# (c) profiler: byte accounting, windows, spec-level fits
# ---------------------------------------------------------------------------

def test_ring_effective_bytes():
    """Effective volumes match the cost model's own ring formulas at unit
    bandwidth — the invariant that makes fitted bandwidth == table entry."""
    from repro.core.cost_model import (all_gather_time, all_reduce_time,
                                       all_to_all_time, p2p_time)
    b, n = 1024.0, 4
    assert ring_effective_bytes("all-reduce", b, n) == pytest.approx(
        all_reduce_time(b, n, 1.0))
    assert ring_effective_bytes("all-gather", b, n) == pytest.approx(
        all_gather_time(b, n, 1.0))
    assert ring_effective_bytes("reduce-scatter", b, n) == pytest.approx(
        all_gather_time(b, n, 1.0))
    assert ring_effective_bytes("all-to-all", b, n) == pytest.approx(
        all_to_all_time(b, n, 1.0))
    assert ring_effective_bytes("p2p", b, n) == pytest.approx(
        p2p_time(b, 1.0))
    assert ring_effective_bytes("all-reduce", b, 1) == 0.0
    with pytest.raises(ValueError):
        ring_effective_bytes("gossip", b, n)


def test_profiler_window_drops_oldest():
    prof = Profiler(max_per_group=8)
    for s in range(20):
        prof.record_compute("g", wall_s=1.0, flops=1e12, step=s)
    assert prof.n_obs("g") == 8
    assert [o.step for o in prof.window("g")] == list(range(12, 20))
    assert [o.step for o in prof.window("g", last_n=3)] == [17, 18, 19]
    prof.clear("g")
    assert prof.n_obs() == 0


def test_profiler_ignores_degenerate_observations():
    prof = Profiler()
    prof.record_compute("g", wall_s=0.0, flops=1e12)
    prof.record_compute("g", wall_s=1.0, flops=0.0)
    prof.record_kernel("g", hbm_bytes=0.0, wall_s=1.0)
    prof.record_collective("g", "all-reduce", 1024.0, 1, 1.0)  # n=1: no-op
    assert prof.n_obs() == 0


def test_profiler_fit_spec_per_group():
    """Two groups with different true rates fit independently; a group
    without observations keeps its prior."""
    spec = ClusterSpec(groups=(DeviceGroup("v", V100_PAPER, 8),
                               DeviceGroup("t", T4_16G, 8),
                               DeviceGroup("idle", TPU_V5E, 8)))
    prof = Profiler()
    for o in synthesize_observations(_meta(), StrategySpec(dp=4, tp=2),
                                     TRUTH, n_steps=16, group="v"):
        prof.record(o)
    for o in synthesize_observations(_meta(), StrategySpec(dp=4, tp=2),
                                     T4_16G, n_steps=16, group="t"):
        prof.record(o)
    out, fits = prof.fit_spec(spec)
    assert set(fits) == {"v", "t"}
    assert parameter_error(out.groups[0].hw, TRUTH) < 1e-3
    assert parameter_error(out.groups[1].hw, T4_16G) < 1e-3
    assert out.groups[2].hw is TPU_V5E
    assert prof.error("v", out.groups[0].hw) < 1e-3
    rep = prof.report(out)
    assert "v" in rep and "idle" in rep and "eff_flops" in rep


# ---------------------------------------------------------------------------
# (d) drift: the ramp scenario and the pricing kernel it re-plans with
# ---------------------------------------------------------------------------

def test_drift_host_ramp():
    d = DriftHost(host=1, start_step=10, end_step=30, factor=3.0)
    assert d.factor_at(0) == 1.0 and d.factor_at(10) == 1.0
    assert d.factor_at(20) == pytest.approx(2.0)
    assert d.factor_at(30) == 3.0 and d.factor_at(100) == 3.0


def test_injector_applies_drift_ramp():
    inj = FaultInjector(scenarios=(DriftHost(host=0, start_step=0,
                                             end_step=10, factor=2.0),),
                        n_hosts=2, jitter=0.0, seed=0, nominal=1.0)
    t5 = inj.host_times(5)
    assert t5[0] == pytest.approx(1.5) and t5[1] == pytest.approx(1.0)
    assert inj.host_times(10)[0] == pytest.approx(2.0)


def test_price_batch_shares_matches_plan_placement():
    """The exposed pricing kernel is byte-identical to what the balancer
    prices internally — re-pricing stale shares on a re-fitted spec uses
    the same arithmetic as planning fresh ones."""
    meta = _meta()
    strat = StrategySpec(dp=8, tp=2)
    spec = ClusterSpec(groups=(DeviceGroup("v100", V100_PAPER, 8),
                               DeviceGroup("t4", T4_16G, 8)))
    pl = plan_placement(meta, strat, spec, overlap=0.5)
    units, extra = price_batch_shares(meta, strat, spec, pl.batch_shares,
                                      overlap=0.5)
    got = [u.cost for u in units]
    want = [u.cost for u in pl.units if u.kind == "group"]
    assert got == want
    assert extra >= 0.0


@pytest.mark.slow
def test_drift_controller_recalibrates_end_to_end(tmp_path):
    """A slow 1→3× ramp on one host (under the straggler monitor's
    outlier band) trips the predicted-vs-measured skew watch; the
    controller re-fits the table from profiler observations, re-plans,
    resumes, and finishes — with the fitted rate reflecting the slowdown
    and no host evicted."""
    run_py(f"""
        from repro.ckpt.checkpoint import CheckpointManager
        from repro.configs import get_config
        from repro.core.cost_model import TPU_V5E
        from repro.data.pipeline import DataCfg, TokenPipeline
        from repro.launch.train import (CalibrationConfig, ElasticConfig,
                                        TrainController)
        from repro.models.lm import build
        from repro.optim import adamw
        from repro.runtime.elastic import HostTopology
        from repro.runtime.faults import DriftHost, FaultInjector

        N = 60
        cfg = get_config("tinyllama-1.1b", smoke=True)
        model = build(cfg)
        data = TokenPipeline(DataCfg(global_batch=8, seq_len=64,
                                     vocab=cfg.vocab, seed=0))
        inj = FaultInjector(scenarios=(
            DriftHost(host=1, start_step=5, end_step=200, factor=3.0),),
            n_hosts=2, seed=0, nominal=0.05)
        ctl = TrainController(
            model, cfg, adamw(lr=1e-3), data,
            CheckpointManager({str(tmp_path)!r} + "/drift", keep=3),
            elastic=ElasticConfig(
                topology=HostTopology.uniform(2, 2, TPU_V5E),
                patience=3, warmup=3,
                calibration=CalibrationConfig(skew=0.25, patience=3,
                                              min_steps=8)),
            batch=8, seq=64, save_every=10, injector=inj, log_every=100)
        out = ctl.run(N, seed=0)
        assert out["phase"] == "DONE" and out["final_step"] == N
        kinds = [e["kind"] for e in out["events"]]
        assert "drift" in kinds and "recalibrate" in kinds, kinds
        assert "evict" not in kinds, kinds      # the ramp must NOT evict
        assert out["topology"].host_ids == (0, 1)
        drift = next(e for e in out["events"] if e["kind"] == "drift")
        assert drift["skew"] > 1.25
        (gname, fitted), = drift["hardware"].items()
        prior_eff = TPU_V5E.peak_flops * TPU_V5E.mxu_eff
        assert fitted["n_obs"] > 0
        assert fitted["eff_flops"] < prior_eff, (fitted, prior_eff)
        print("OK drift→recalibrate:", drift["skew"], fitted)
    """)
