"""Pipeline scheduling subsystem (repro.core.schedule + the executors).

Three layers of evidence:

1. **Table properties** (pure Python, random (S, M)): every schedule runs
   each (stage, micro-batch) fwd and bwd exactly once, respects the
   pipeline dependencies, spans 2·(M+S−1) ticks with the closed-form
   bubble (S−1)/(M+S−1), and 1F1B's peak in-flight activations are
   ≤ min(S, M) while GPipe's are exactly M.
2. **Schedule equivalence** (single device, f32 smoke model): the
   order-faithful interpreter (`pipeline.schedule_grads`) reproduces the
   single-device reference loss and gradients for even *and* uneven
   stage splits, GPipe and 1F1B produce identical results on the same
   params/tokens (schedule changes order, not math), and the measured
   activation-buffer high-water mark matches the schedule's accounting.
3. **Edges**: the B % micro_batches guard raises a clear ValueError
   everywhere a truncated reshape used to lurk, and the uneven param
   pad/unpad round-trips.
"""
import dataclasses
import random

import pytest

from repro.core.schedule import (FWD, BWD, Schedule, SCHEDULE_NAMES,
                                 bubble_fraction_closed_form,
                                 gpipe_schedule, in_flight_micro_batches,
                                 make_schedule, one_f_one_b_schedule)


def random_cases(n=25, seed=0):
    rng = random.Random(seed)
    cases = [(2, 2), (2, 8), (4, 4), (4, 1), (1, 4), (3, 5), (8, 2)]
    while len(cases) < n:
        cases.append((rng.randint(1, 8), rng.randint(1, 16)))
    return cases


# ---------------------------------------------------------------------------
# 1. table properties over random (S, M)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", SCHEDULE_NAMES)
def test_every_unit_scheduled_exactly_once(name):
    for S, M in random_cases():
        sc = make_schedule(name, S, M)
        seen = {}
        for _, s, mb, phase in sc.slots():
            key = (s, mb, phase)
            assert key not in seen, f"{name} S={S} M={M}: {key} twice"
            seen[key] = True
        assert len(seen) == 2 * S * M, \
            f"{name} S={S} M={M}: {len(seen)} slots, expected {2 * S * M}"


@pytest.mark.parametrize("name", SCHEDULE_NAMES)
def test_dependencies_respected(name):
    """fwd s−1 before fwd s; bwd s+1 before bwd s; own fwd before bwd —
    re-checked here independently of Schedule.validate()."""
    for S, M in random_cases():
        sc = make_schedule(name, S, M)
        when = {(s, mb, ph): t for t, s, mb, ph in sc.slots()}
        for s in range(S):
            for mb in range(M):
                if s > 0:
                    assert when[(s - 1, mb, FWD)] < when[(s, mb, FWD)]
                if s < S - 1:
                    assert when[(s + 1, mb, BWD)] < when[(s, mb, BWD)]
                assert when[(s, mb, FWD)] < when[(s, mb, BWD)]


@pytest.mark.parametrize("name", SCHEDULE_NAMES)
def test_at_most_one_slot_per_stage_per_tick(name):
    for S, M in random_cases(10, seed=3):
        sc = make_schedule(name, S, M)
        for row in sc.ticks:
            assert len(row) == S     # one cell per stage, idle cells None


def test_peak_in_flight_gpipe_all_1f1b_capped():
    """The memory headline: GPipe buffers all M micro-batches, 1F1B never
    more than min(S, M) — table-measured AND matching the closed forms
    the cost model prices with."""
    for S, M in random_cases():
        g = gpipe_schedule(S, M)
        f = one_f_one_b_schedule(S, M)
        assert g.peak_in_flight() == M
        assert f.peak_in_flight() <= min(S, M)
        assert g.peak_in_flight() == in_flight_micro_batches(S, M, "gpipe")
        assert f.peak_in_flight() == in_flight_micro_batches(S, M, "1f1b")
        if M >= S:
            # per-stage cap is exactly min(S − s, M): stage 0 is tightest
            assert f.per_stage_in_flight()[0] == S


@pytest.mark.parametrize("name", SCHEDULE_NAMES)
def test_bubble_fraction_matches_closed_form(name):
    for S, M in random_cases():
        sc = make_schedule(name, S, M)
        assert sc.bubble_fraction() == pytest.approx(
            bubble_fraction_closed_form(S, M), abs=1e-12)
        assert sc.n_ticks == 2 * (M + S - 1)


def test_validate_catches_broken_tables():
    good = gpipe_schedule(2, 2)
    # drop one bwd slot → incomplete
    ticks = list(good.ticks)
    ticks[-1] = (None, None)
    with pytest.raises(ValueError, match="never runs"):
        Schedule("broken", 2, 2, tuple(ticks)).validate()
    # swap the two forward waves stage-wise → dependency violation
    bad = tuple(tuple(reversed(row)) for row in good.ticks)
    with pytest.raises(ValueError):
        Schedule("swapped", 2, 2, bad).validate()


def test_unknown_schedule_rejected():
    with pytest.raises(ValueError, match="unknown schedule"):
        make_schedule("interleaved-zb", 4, 8)
    with pytest.raises(ValueError, match="unknown schedule"):
        in_flight_micro_batches(4, 8, "interleaved-zb")


# ---------------------------------------------------------------------------
# 2. schedule equivalence through the interpreter (single device, f32)
# ---------------------------------------------------------------------------

def _f32_model(n_layers=4):
    from repro.configs import get_config
    from repro.models.lm import build
    # f32 activations → tight tolerances; remat off → the eager interpreter
    # does not re-trace each checkpointed repeat (pure test-speed choice)
    cfg = dataclasses.replace(get_config("tinyllama-1.1b", smoke=True),
                              n_layers=n_layers, dtype="float32",
                              remat="none", name="sched-f32")
    return build(cfg)


_RUNS = {}


def _interpreter_run(name):
    """Shared (model, params, tokens, reference, interpreter) results for
    the even-split equivalence tests — computed once per schedule."""
    if name not in _RUNS:
        import jax
        from repro.core.pipeline import schedule_grads
        model = _f32_model()
        params = model.init(jax.random.key(0))
        tokens = _tokens(model)
        ref = _reference(model, params, tokens)
        out = schedule_grads(model, params, tokens, micro_batches=4,
                             schedule=name, n_stages=2)
        _RUNS[name] = (ref, out)
    return _RUNS[name]


def _tokens(model, B=8, T=16, seed=0):
    import jax.numpy as jnp
    import numpy as np
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, model.cfg.vocab, (B, T)), jnp.int32)


def _reference(model, params, tokens):
    import jax
    (loss, _), grads = jax.value_and_grad(
        model.loss_fn, has_aux=True)(params, {"tokens": tokens})
    return loss, grads


def _assert_trees_close(a, b, rtol=2e-4, atol=2e-5):
    import jax
    import numpy as np
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)


@pytest.mark.parametrize("name", SCHEDULE_NAMES)
def test_interpreter_matches_single_device_reference(name):
    """Pipelined loss AND grads == the non-pipelined reference."""
    import numpy as np
    (l_ref, g_ref), (loss, grads, stats) = _interpreter_run(name)
    np.testing.assert_allclose(float(loss), float(l_ref), rtol=1e-5)
    _assert_trees_close(g_ref, grads)
    assert stats["bubble_fraction"] == pytest.approx(
        bubble_fraction_closed_form(2, 4))


def test_gpipe_and_1f1b_identical_losses_and_grads():
    """Schedule changes order, not math: same params/tokens → same step."""
    import numpy as np
    _, (lg, gg, sg) = _interpreter_run("gpipe")
    _, (lf, gf, sf) = _interpreter_run("1f1b")
    np.testing.assert_allclose(float(lg), float(lf), rtol=1e-6)
    _assert_trees_close(gg, gf, rtol=1e-5, atol=1e-7)
    # ...while the memory profiles genuinely differ
    assert sg["peak_in_flight"] == 4 and sf["peak_in_flight"] == 2


@pytest.mark.parametrize("stage_layers", [(3, 1), (1, 2, 1)])
def test_uneven_stages_match_reference(stage_layers):
    """The tentpole numerics: latency-equalizing *uneven* layer splits
    (what HeteroPlacement.layer_alloc produces) change nothing about the
    math."""
    import jax
    import numpy as np
    from repro.core.pipeline import schedule_grads
    model = _f32_model(n_layers=sum(stage_layers))
    params = model.init(jax.random.key(2))
    tokens = _tokens(model, seed=2)
    l_ref, g_ref = _reference(model, params, tokens)
    loss, grads, stats = schedule_grads(model, params, tokens,
                                        micro_batches=2, schedule="1f1b",
                                        stage_layers=stage_layers)
    np.testing.assert_allclose(float(loss), float(l_ref), rtol=1e-5)
    _assert_trees_close(g_ref, grads)
    assert stats["stage_layers"] == tuple(stage_layers)


def test_interpreter_buffer_audit_matches_schedule_accounting():
    """schedule_grads measures its live activation buffer per stage and
    fails loudly if it disagrees with Schedule.per_stage_in_flight — here
    we confirm the measured numbers surface correctly."""
    for name in SCHEDULE_NAMES:
        sc = make_schedule(name, 2, 4)
        _, (_, _, stats) = _interpreter_run(name)
        assert stats["per_stage_in_flight"] == sc.per_stage_in_flight()
        assert stats["n_ticks"] == sc.n_ticks


# ---------------------------------------------------------------------------
# 3. edges: B % M guard, pad/unpad round-trip, alloc mapping
# ---------------------------------------------------------------------------

def test_batch_not_divisible_by_micro_batches_raises():
    """Regression: the old truncated reshape path must be a loud error."""
    import jax
    from repro.core.pipeline import schedule_grads
    model = _f32_model()
    params = model.init(jax.random.key(0))
    tokens = _tokens(model, B=7)
    with pytest.raises(ValueError, match="micro_batches"):
        schedule_grads(model, params, tokens, micro_batches=4,
                       schedule="1f1b", n_stages=2)


def test_grad_accumulation_batch_guard_in_planner():
    """Same edge through ExecutionPlan.train_step_fn's accumulator."""
    import jax
    import jax.numpy as jnp
    from repro.core.planner import compile_plan
    from repro.core.sharding import make_mesh
    from repro.optim.optimizer import adamw
    model = _f32_model(n_layers=2)
    mesh = make_mesh((1,), ("data",))
    plan = compile_plan(model, mesh)
    fn = plan.train_step_fn(adamw(lr=1e-3), micro_batches=3)
    params = model.init(jax.random.key(0))
    opt_state = adamw(lr=1e-3).init(params)
    batch = {"tokens": _tokens(model, B=8)}
    with mesh:
        with pytest.raises(ValueError, match="silently drop"):
            jax.eval_shape(fn, params, opt_state, batch,
                           jnp.zeros((), jnp.int32))


def test_pad_unpad_round_trip_and_zero_grad_rows():
    import jax
    import numpy as np
    from repro.core.pipeline import pad_stage_stack, unpad_stage_stack
    model = _f32_model(n_layers=4)
    blocks = model.init(jax.random.key(0))["blocks"]
    for sl in ((3, 1), (1, 2, 1), (2, 2)):
        padded = pad_stage_stack(blocks, sl)
        lmax = max(sl)
        for leaf in jax.tree.leaves(padded):
            assert leaf.shape[0] == len(sl) * lmax
        rt = unpad_stage_stack(padded, sl)
        for a, b in zip(jax.tree.leaves(rt), jax.tree.leaves(blocks)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_stage_layers_validation_and_alloc_mapping():
    from repro.core.pipeline import (check_stage_layers, even_stage_layers,
                                     stage_layers_from_alloc)
    model = _f32_model(n_layers=8)
    assert even_stage_layers(8, 4) == (2, 2, 2, 2)
    with pytest.raises(ValueError, match="not divisible"):
        even_stage_layers(8, 3)
    with pytest.raises(ValueError, match="sums to"):
        check_stage_layers((3, 3), 8, 2)
    with pytest.raises(ValueError, match=">= 1"):
        check_stage_layers((8, 0), 8, 2)
    assert stage_layers_from_alloc(model.stack, (3, 3, 1, 1)) == (3, 3, 1, 1)


def test_cost_model_prices_1f1b_memory_below_gpipe():
    """The search's tie-breaker: same bubble, smaller activation term."""
    from repro.configs import get_config
    from repro.core.cost_model import (StrategySpec, TPU_V5E,
                                       step_cost)
    from repro.models.lm import model_graph
    meta = model_graph(get_config("tinyllama-1.1b"), 64, 512).workload_meta()
    g = step_cost(meta, StrategySpec(dp=8, pp=2, micro_batches=8,
                                     schedule="gpipe"), TPU_V5E)
    f = step_cost(meta, StrategySpec(dp=8, pp=2, micro_batches=8,
                                     schedule="1f1b"), TPU_V5E)
    assert f.mem_bytes < g.mem_bytes
    assert f.bubble == g.bubble
    assert f.compute == g.compute


def test_auto_search_enumerates_both_schedules():
    from repro.configs import get_config
    from repro.core.auto import enumerate_strategies
    from repro.models.lm import model_graph
    meta = model_graph(get_config("tinyllama-1.1b"), 256, 512).workload_meta()
    scheds = {(s.pp > 1, s.schedule)
              for s in enumerate_strategies(meta, 8)}
    assert (True, "gpipe") in scheds and (True, "1f1b") in scheds
    assert (False, "1f1b") not in scheds     # schedule only matters for pp>1


def test_gpipe_aliases_are_gone():
    """The pre-schedule-subsystem make_gpipe_* shims (deprecated since the
    schedule subsystem landed) are removed; make_pipeline_* is the API."""
    import repro.core.pipeline as pipe
    assert not hasattr(pipe, "make_gpipe_loss")
    assert not hasattr(pipe, "make_gpipe_train_step")
    assert callable(pipe.make_pipeline_loss)
    assert callable(pipe.make_pipeline_train_step)
