"""Quick substrate check: every smoke config does one fwd loss + one decode step."""
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_NAMES, get_config
from repro.configs import shapes as sh
from repro.models.lm import build, param_count

key = jax.random.key(0)
for name in ARCH_NAMES:
    t0 = time.time()
    cfg = get_config(name, smoke=True)
    model = build(cfg)
    params = model.init(key)
    n = param_count(params)
    cell = sh.ShapeCell("t", "train", 64, 2)
    batch = sh.make_synthetic_batch(model, cell, key)
    loss, metrics = jax.jit(model.loss_fn)(params, batch)
    assert jnp.isfinite(loss), (name, loss)
    # decode one step
    state = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        model.decode_state_shapes(2, 32))
    logits, state2 = jax.jit(model.serve_step)(params, jnp.zeros((2,), jnp.int32), state)
    assert jnp.all(jnp.isfinite(logits)), name
    # axes treedef matches params treedef
    axes = model.axes()
    jax.tree.map(lambda p, a: None, params, axes,
                 is_leaf=lambda t: isinstance(t, tuple) and all(
                     isinstance(e, (str, type(None))) for e in t))
    print(f"{name:24s} params={n:9d} loss={float(loss):8.4f} "
          f"({time.time()-t0:.1f}s)")

# heterogeneous planner smoke: the fig7 benchmark's analytic comparison
# (hardware-aware vs naive even split) with its built-in assertions
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import benchmarks.fig7_heterogeneous as fig7
fig7.main()

# pipeline schedule smoke: tick tables validate against the closed forms
# (full property coverage in tests/test_schedule.py) + the fig2 grid's
# built-in assertions (same bubble, 1F1B memory advantage, uneven >= even)
from repro.core.schedule import (bubble_fraction_closed_form, make_schedule)
for S, M in ((2, 4), (4, 8), (3, 5)):
    for name in ("gpipe", "1f1b"):
        sc = make_schedule(name, S, M)
        assert abs(sc.bubble_fraction()
                   - bubble_fraction_closed_form(S, M)) < 1e-12
import benchmarks.fig2_bert_pipeline as fig2
fig2.print_schedule_grid(fig2.schedule_grid_rows())

# nested-hybrid smoke: the fig9 M6 comparison (flat DP OOMs, nested DP×EP
# fits and wins) with its built-in assertions, plus the graph optimizer's
# bridge insertion on a traced replica{split[experts]} nest
import benchmarks.fig9_m6_moe as fig9
fig9.main()

# multimodal smoke: the fig10 M6 comparison (segment-aware auto-search
# beats the hand-even pipeline split; jamba-52B feasible only via auto)
# with its built-in assertions
import benchmarks.fig10_multimodal as fig10
fig10.main()

# self-healing smoke: the fig_elastic eviction loop (straggler detected,
# evicted, rebalanced plan recovers to the cost-model prediction) with its
# built-in assertions
import benchmarks.fig_elastic as fig_elastic
fig_elastic.main()
# spot-fleet smoke: the fig_spot drain-and-grow vs restart comparison
# (hosts shed within the reclaim deadline, re-admitted later, post-grow
# back on the full-fleet prediction) with its built-in assertions
import benchmarks.fig_spot as fig_spot
fig_spot.main()
# serving smoke: the fig_serve paged+disaggregated comparison with its
# built-in gates (≥1.3× tokens/s, p99 TTFT no worse), plus one real
# paged-vs-dense lockstep decode step proving bit-exactness end to end
import benchmarks.fig_serve as fig_serve
fig_serve.main()
# calibration smoke: the fig_calibration fit + drift comparison with its
# built-in gates (fit error ≤10%, continuous rebalance ≥1.3× one-shot)
import benchmarks.fig_calibration as fig_cal
fig_cal.main()

import numpy as np
import repro as wh
from repro.core.planner import compile_plan
from repro.serving.server import Request, Server

_cfg = get_config("tinyllama-1.1b", smoke=True)
_model = build(_cfg)
_mesh = wh.make_mesh((len(jax.devices()),), ("data",))
_plan = compile_plan(_model, _mesh)
with _mesh:
    _params = _plan.init_params(jax.random.key(0))
_srvs = {c: Server(_model, _plan, batch_slots=2, max_len=16, cache=c,
                   page_size=4, record_logits=True)
         for c in ("dense", "paged")}
for _c, _srv in _srvs.items():
    _srv.admit(_params, Request(0, np.arange(5, dtype=np.int32), max_new=4),
               slot=0)
    _srv.step(_params)
assert _srvs["dense"].slots[0].out_tokens \
    == _srvs["paged"].slots[0].out_tokens
assert np.array_equal(_srvs["dense"].last_logits[0],
                      _srvs["paged"].last_logits[0])
print("serving: paged decode bit-exact vs dense")

import repro as wh
with wh.cluster(mesh_shape=(1, 1), axis_names=("data", "model")) as _cl:
    with wh.replica():
        _h = wh.sub("attn", lambda p, x: x @ p["w"])(
            {"w": jnp.ones((8, 8))}, jnp.ones((4, 8)))
        with wh.split(experts=True):
            _h = wh.sub("moe", lambda p, x: x @ p["w"])(
                {"w": jnp.ones((8, 8))}, _h)
_low = wh.lower(_cl)
assert _low.bridges("all_to_all"), _low.describe()
assert _low.max_nesting_depth == 2
print("graph_opt:", _low.describe())
print("ALL OK")
