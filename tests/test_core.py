"""Whale core: IR capture, strategy scopes, sharding rules, cost model, auto."""
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

import repro as wh
from repro.core.auto import enumerate_strategies, search
from repro.core.cost_model import (StrategySpec, TPU_V5E, V100_PAPER,
                                   WorkloadMeta, all_gather_time,
                                   all_reduce_time, device_hardware,
                                   step_cost)
from repro.core.ir import TaskGraph, TensorMeta, capture_meta, jaxpr_flops
from repro.core.sharding import constrain, hybrid_rules, make_mesh, use_rules
from repro.models.lm import model_graph


# ---------------------------------------------------------------------------
# IR: meta capture is abstract + FLOPs are trip-count exact
# ---------------------------------------------------------------------------

def test_capture_meta_no_execution():
    calls = []

    def fn(x):
        calls.append(1)        # traced once; never executed
        return x @ x.T

    x = jnp.ones((8, 4))
    inputs, outputs, flops, _ = capture_meta(fn, x)
    assert outputs[0].shape == (8, 8)
    assert flops == 2 * 8 * 8 * 4


def test_jaxpr_flops_counts_scan_trips():
    def f(x):
        def body(c, _):
            return c @ jnp.eye(16), None
        out, _ = jax.lax.scan(body, x, None, length=7)
        return out

    jaxpr = jax.make_jaxpr(f)(jnp.ones((16, 16)))
    assert jaxpr_flops(jaxpr.jaxpr) == 7 * 2 * 16 * 16 * 16


def test_jaxpr_flops_counts_remat_body():
    def f(x):
        return jax.checkpoint(lambda y: y @ y)(x).sum()

    jaxpr = jax.make_jaxpr(f)(jnp.ones((8, 8)))
    assert jaxpr_flops(jaxpr.jaxpr) == 2 * 8 * 8 * 8
    gjax = jax.make_jaxpr(jax.grad(f))(jnp.ones((8, 8)))
    # grad of remat: fwd + recompute + 2 transpose dots
    assert jaxpr_flops(gjax.jaxpr) >= 3 * 2 * 8 * 8 * 8


def test_cluster_repeats_groups_identical_layers():
    tg = TaskGraph()
    for i in range(5):
        sg = wh.Subgraph(name=f"l{i}", fn=None, strategy=[],
                         params=[TensorMeta((4, 4), jnp.float32)],
                         outputs=[TensorMeta((2, 4), jnp.float32)])
        tg.add(sg)
    tg.add(wh.Subgraph(name="head", fn=None, strategy=[],
                       params=[TensorMeta((4, 100), jnp.float32)],
                       outputs=[TensorMeta((2, 100), jnp.float32)]))
    groups = tg.cluster_repeats()
    assert len(groups) == 2
    assert len(groups[0]["nodes"]) == 5


# ---------------------------------------------------------------------------
# strategy scopes → IR → inferred StrategySpec
# ---------------------------------------------------------------------------

def test_scopes_record_and_infer():
    def net(params, x):
        return x @ params["w"]

    params = {"w": jnp.ones((4, 8))}
    with wh.cluster(mesh_shape=(1, 1), axis_names=("data", "model")) as cl:
        with wh.replica():
            h = wh.sub("backbone", net)(params, jnp.ones((2, 4)))
        with wh.split(dim=-1):
            wh.sub("fc", net)({"w": jnp.ones((8, 16))}, h)
    names = [n.name for n in cl.taskgraph.nodes]
    assert names == ["backbone", "fc"]
    assert cl.taskgraph.by_name("backbone").strategy_kinds() == ("replica",)
    assert cl.taskgraph.by_name("fc").strategy_kinds() == ("split",)
    # param metadata split from data inputs (first dict arg convention)
    assert cl.taskgraph.by_name("fc").params[0].shape == (8, 16)
    strat = wh.strategy_from_taskgraph(cl)
    assert strat.vocab_split
    assert strat.dp == 1 and strat.tp == 1


def test_pipeline_scope_records_stages_and_micro():
    with wh.cluster(mesh_shape=(1,), axis_names=("data",)) as cl:
        with wh.replica():
            with wh.pipeline(micro_batch=6):
                with wh.stage():
                    wh.sub("s0", lambda x: x * 1.0)(jnp.ones(3))
                with wh.stage():
                    wh.sub("s1", lambda x: x * 2.0)(jnp.ones(3))
    strat = wh.strategy_from_taskgraph(cl)
    assert strat.micro_batches == 6
    idx = [next(a.options["index"] for a in n.strategy if a.kind == "stage")
           for n in cl.taskgraph.nodes]
    assert idx == [0, 1]


# ---------------------------------------------------------------------------
# sharding rules: divisibility pruning + axis reuse (property)
# ---------------------------------------------------------------------------

def _mesh(shape, names):
    return make_mesh(shape, names)


def test_make_mesh_axes_are_auto():
    """Explicit axes (jax's default) refuse the rules' sharding constraints."""
    mesh = make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,) * 2
    with mesh, use_rules(hybrid_rules(mesh)):
        y = jax.jit(lambda x: constrain(x * 2, ("batch", None)))(
            jnp.ones((4, 8)))
    assert float(y.sum()) == 64.0


class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


@pytest.mark.parametrize("platform,kind,want", [
    ("tpu", "TPU v5 lite", TPU_V5E),
    ("cpu", "cpu", TPU_V5E),            # CPU runs plan for the target part
    ("tpu", "TPU v99", None),           # an unknown TPU is not planned as v5e
])
def test_device_hardware_from_device_kind(monkeypatch, platform, kind, want):
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDevice(platform, kind)])
    if want is None:
        with pytest.raises(RuntimeError, match="TPU v99"):
            device_hardware()
    else:
        assert device_hardware() is want


def test_spec_for_prunes_non_divisible():
    rules = hybrid_rules(_mesh((1, 1), ("data", "model")))
    rules.mesh = _FakeMesh({"data": 4, "model": 16})
    # kv_heads=8 does not divide 16 → replicated
    spec = rules.spec_for(("batch", None, "kv_heads", None), (32, 1, 8, 64))
    assert spec == P("data", None, None, None)
    # q_heads=32 divides → sharded
    spec = rules.spec_for(("batch", None, "q_heads", None), (32, 1, 32, 64))
    assert spec == P("data", None, "model", None)


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_param_spec_fsdp_picks_largest_free_dim():
    rules = hybrid_rules(_mesh((1, 1), ("data", "model")))
    rules.mesh = _FakeMesh({"data": 8, "model": 4})
    spec = rules.param_spec(("embed", "mlp"), (1024, 4096),
                            fsdp_axes=("data",))
    assert spec == P("data", "model")          # mlp→model, fsdp takes embed
    # small tensors are not FSDP-sharded
    spec = rules.param_spec(("embed",), (128,), fsdp_axes=("data",))
    assert spec == P(None)


@settings(max_examples=50, deadline=None)
@given(
    dims=st.lists(st.sampled_from(
        ["batch", "embed", "q_heads", "kv_heads", "mlp", "vocab", None]),
        min_size=1, max_size=4),
    shape=st.lists(st.sampled_from([1, 2, 3, 8, 16, 30, 64, 256]),
                   min_size=1, max_size=4),
)
def test_spec_property_legal(dims, shape):
    """Property: spec_for never reuses a mesh axis and only shards dims
    the axis size divides."""
    n = min(len(dims), len(shape))
    dims, shape = dims[:n], shape[:n]
    rules = hybrid_rules(_mesh((1, 1), ("data", "model")))
    rules.mesh = _FakeMesh({"data": 4, "model": 16, "pod": 2})
    spec = rules.spec_for(dims, shape)
    used = []
    for i, p in enumerate(spec):
        axes = (p,) if isinstance(p, str) else (p or ())
        for a in axes:
            assert a not in used, f"axis {a} reused in {spec}"
            used.append(a)
        if axes:
            sz = 1
            for a in axes:
                sz *= rules.mesh.shape[a]
            assert shape[i] % sz == 0


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------

def test_collective_formulas():
    assert all_reduce_time(100.0, 1, 10.0) == 0.0
    assert all_reduce_time(100.0, 4, 10.0) == pytest.approx(15.0)
    assert all_gather_time(100.0, 4, 10.0) == pytest.approx(7.5)


def test_step_cost_memory_decreases_with_zero():
    meta = model_graph(__import__("repro.configs", fromlist=["get_config"]) .get_config("tinyllama-1.1b"), 256, 2048).workload_meta()
    c0 = step_cost(meta, StrategySpec(dp=64, zero=0), TPU_V5E)
    c3 = step_cost(meta, StrategySpec(dp=64, zero=3), TPU_V5E)
    assert c3.mem_bytes < c0.mem_bytes


def test_step_cost_pipeline_bubble():
    meta = model_graph(__import__("repro.configs", fromlist=["get_config"]) .get_config("tinyllama-1.1b"), 64, 512).workload_meta()
    c1 = step_cost(meta, StrategySpec(dp=8, pp=2, micro_batches=1), TPU_V5E)
    c8 = step_cost(meta, StrategySpec(dp=8, pp=2, micro_batches=8), TPU_V5E)
    assert c8.bubble < c1.bubble


def test_vocab_split_beats_gathered_head_on_paper_hw():
    """The Fig-4 technique must win for a giant classifier head."""
    meta = WorkloadMeta(
        name="cls", fwd_flops=1e12, param_bytes=872e6 * 4,
        tp_shardable_param_bytes=782e6 * 4, act_bytes_per_layer=1e6,
        n_layers=50, batch=256, logits_bytes=256 * 1e5 * 4,
        head_param_bytes=782e6 * 4)
    with_split = step_cost(meta, StrategySpec(dp=8, tp=8, vocab_split=True),
                           V100_PAPER)
    without = step_cost(meta, StrategySpec(dp=8, tp=8, vocab_split=False),
                        V100_PAPER)
    assert with_split.comm < without.comm


# ---------------------------------------------------------------------------
# auto-parallel search
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(devices=st.sampled_from([8, 16, 64, 256]))
def test_enumeration_is_pruned_and_legal(devices):
    from repro.configs import get_config
    meta = model_graph(get_config("tinyllama-1.1b"), 256, 512).workload_meta()
    for s in enumerate_strategies(meta, devices):
        assert s.dp * s.tp * s.pp == devices
        assert meta.n_layers % s.pp == 0
        assert meta.batch % s.dp == 0


def test_search_returns_sorted_feasible():
    from repro.configs import get_config
    meta = model_graph(get_config("qwen3-1.7b"), 256, 4096).workload_meta()
    cands = search(meta, 256, TPU_V5E, top_k=8)
    assert cands, "no feasible strategy found"
    totals = [c.total for c in cands]
    assert totals == sorted(totals)
    assert all(c.cost.feasible for c in cands)


def test_auto_parallel_prefers_fitting_strategy_for_giant_model():
    from repro.configs import get_config
    meta = model_graph(get_config("grok-1-314b"), 256, 4096).workload_meta()
    strat = wh.auto_parallel(meta, 256, TPU_V5E)
    # 314B params cannot be pure DP on 16 GB chips
    assert strat.tp > 1 or strat.pp > 1 or strat.zero >= 3
