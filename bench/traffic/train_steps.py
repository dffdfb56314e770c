"""Generator ``train_steps``: the program's jitted train step, driven by
the benchmark in a time-bounded loop on seeded token batches.

The step is built as ``repro.launch.train`` builds it (configuration →
``build`` → ``compile_plan(...).jit_train_step``), with the weights and
optimizer state made by the benchmark from the seed, directly into the
plan's shardings.  Set-up drives that one step object through its first
three steps (the readings that decide ``correct``), warms up until a step
takes as long as the one before it, and hands the same object to the
window.  The window keeps one step in flight: it dispatches step i, then
reads step i-1's loss.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.core.cost_model import device_hardware
from repro.core.planner import compile_plan
from repro.core.sharding import make_mesh
from repro.kernels.autotune import autotune
from repro.models.lm import LMCfg, build as build_model
from repro.optim.optimizer import adamw

from bench import harness, weights
from bench.reference import train as ref_train
from bench.traffic import tokens as tok

TRAIN_STREAM = 1          # token stream of the training batches
CHECK_STEPS = 3           # steps the reference follows
WARMUP_MAX = 8            # further warm-up steps at most
STEADY = 0.05             # a step within 5% of the one before is steady


@dataclasses.dataclass
class Program:
    mesh: object
    plan: object
    opt: object
    step: object
    batch_sharding: object
    B: int
    S: int
    vocab: int
    b1: float
    params: object = None
    opt_state: object = None
    next_step: int = 0


def lm_config(config: dict, params: dict):
    """The program's LMCfg for a configuration file and a cell."""
    kw = dict(config["program"])
    kw["attn_impl"] = params.get("attn", "ref")
    kw["xent_impl"] = params.get("xent", "ref")
    cfg = LMCfg(**kw)
    if "pallas" in (cfg.attn_impl, cfg.xent_impl, cfg.ssd_impl):
        # kernel tiles for the chip in use, as repro.launch.train sizes them;
        # the SSD keeps the configuration's chunk (it runs on the XLA path)
        hw = device_hardware() if jax.devices()[0].platform == "tpu" else None
        t = autotune(hw, head_dim=cfg.hd if cfg.n_heads else cfg.ssd_headdim,
                     group=cfg.n_heads // max(cfg.n_kv_heads, 1) or 1,
                     d_model=cfg.d_model, vocab=cfg.padded_vocab,
                     seq=params["seq_len"])
        cfg = dataclasses.replace(cfg, attn_block_q=t.block_q,
                                  attn_block_k=t.block_k,
                                  xent_block_t=t.xent_block_t,
                                  xent_block_v=t.xent_block_v)
    return cfg


def optimizer(mix: dict):
    o = mix["optimizer"]
    return adamw(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                 weight_decay=o["weight_decay"],
                 max_grad_norm=o["max_grad_norm"])


def build(cell: harness.Cell, config: dict, devs, *, fault=None) -> Program:
    """The program's train step for the cell (with ``fault`` planted, for
    the tests), its plan and its optimizer."""
    p = dict(cell.params, seq_len=cell.mix["seq_len"])
    cfg = lm_config(config, p)
    mesh = make_mesh((len(devs),), ("data",), devices=devs)
    plan = compile_plan(build_model(cfg), mesh)
    ref = cell.reference
    weights.check_layout(ref.shapes(config), plan.param_shapes)
    opt = optimizer(cell.mix)
    B, S = int(p["batch"]), int(cell.mix["seq_len"])
    tree = {"tokens": jax.ShapeDtypeStruct((B, S), np.int32)}
    bsh = plan.batch_shardings(tree)["tokens"]
    with mesh:
        step = (plan.jit_train_step(opt, tree) if fault is None
                else _faulty(plan, opt, fault))
    return Program(mesh=mesh, plan=plan, opt=opt, step=step,
                   batch_sharding=bsh, B=B, S=S,
                   vocab=config["vocab_size"],
                   b1=cell.mix["optimizer"]["b1"])


def _faulty(plan, opt, fault):
    """The train step with one fault planted, for the tests that show a
    broken timed path reads ``correct: false``."""
    fn = plan.train_step_fn(opt)
    if fault == "frozen":                  # the state comes back unchanged
        def step(p, o, batch, i):
            return p, o, fn(p, o, batch, i)[2]
    elif fault == "half_batch":     # half of the rows left out, the mean
        def step(p, o, batch, i):
            half = {"tokens": batch["tokens"][: batch["tokens"].shape[0] // 2]}
            return fn(p, o, half, i)
    else:
        raise ValueError(f"unknown fault {fault!r}")
    return jax.jit(step)


def initial_state(prog: Program, config: dict, ref, seed: int) -> None:
    """Weights from the seed and a zero optimizer state, made directly into
    the shardings the step takes (so its first and later calls run the
    same compiled program)."""
    with prog.mesh:
        prog.params = weights.make(ref.shapes(config), ref.init_rules(config),
                                   seed, shardings=prog.plan.param_shardings)
        osh = jax.tree.map(lambda s: NamedSharding(prog.mesh, s),
                           prog.plan.opt_specs(prog.opt),
                           is_leaf=lambda s: isinstance(
                               s, jax.sharding.PartitionSpec))
        prog.opt_state = jax.jit(prog.opt.init, out_shardings=osh)(
            prog.params)
    prog.next_step = 0


def batch(prog: Program, seed: int, i: int):
    rows = tok.rows(seed, TRAIN_STREAM, i, prog.B, prog.S, prog.vocab)
    return {"tokens": jax.device_put(rows, prog.batch_sharding)}


def call(prog: Program, b):
    """One step through the window's own call; returns the metrics."""
    i = prog.next_step
    with prog.mesh:
        prog.params, prog.opt_state, m = prog.step(
            prog.params, prog.opt_state, b, np.int32(i))
    prog.next_step = i + 1
    return m


def _leaf_change(x, lo, hi, *, path, shape, rule):
    """‖x − the leaf's initial value‖, the initial value made anew."""
    x0 = weights.leaf(weights.leaf_key(lo, hi, path), shape, rule)
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) - x0)))


def first_steps(prog: Program, config: dict, ref, seed: int) -> dict:
    """Drive the step through its first CHECK_STEPS steps on distinct
    rows; read each loss, the first gradient as the optimizer received
    it (its first moment after one step, over 1 − b1), and each leaf's
    change after the last step."""
    b1 = prog.b1
    losses, grad = [], None
    norm = jax.jit(lambda t: {p: jnp.sqrt(jnp.sum(jnp.square(v)))
                              for p, v in weights.flatten(t).items()})
    for i in range(CHECK_STEPS):
        m = call(prog, batch(prog, seed, i))
        losses.append(float(m["loss"]))
        if grad is None:
            grad = {p: float(v) / (1 - b1)
                    for p, v in norm(prog.opt_state["mu"]).items()}
    shapes, rules = ref.shapes(config), ref.init_rules(config)
    lo, hi = weights.seed_words(seed)
    change = {}
    for path, x in weights.flatten(prog.params).items():
        f = _leaf_change_jit(path, tuple(shapes[path]), tuple(rules[path]))
        change[path] = float(f(x, lo, hi))
    return {"loss": losses, "grad": grad, "change": change}


@functools.lru_cache(maxsize=None)
def _leaf_change_jit(path, shape, rule):
    return jax.jit(functools.partial(_leaf_change, path=path, shape=shape,
                                     rule=rule))


def warm_up(prog: Program, seed: int) -> list:
    """Further steps until one takes as long as the one before (within
    STEADY), at most WARMUP_MAX.  Returns their seconds."""
    times = []
    for _ in range(WARMUP_MAX):
        t = time.perf_counter()
        float(call(prog, batch(prog, seed, prog.next_step))["loss"])
        times.append(time.perf_counter() - t)
        if len(times) >= 2 and abs(times[-1] - times[-2]) <= STEADY * times[-2]:
            break
    return times


def window(prog: Program, seed: int, seconds: float,
           tw: harness.TraceWindow) -> dict:
    """The measured window: steps until ``seconds`` have passed, one in
    flight, every loss read back.  Returns steps, losses and seconds."""
    losses, pending = [], None
    n = 0
    t0 = time.perf_counter()
    tw.start()
    while True:
        with tw.span("data_fetch"):
            b = batch(prog, seed, prog.next_step)
        with tw.span("step_dispatch"):
            m = call(prog, b)
        if pending is not None:
            with tw.span("loss_readback"):
                losses.append(float(pending))
        pending = m["loss"]
        n += 1
        if tw.due():
            with tw.span("loss_readback"):
                losses.append(float(pending))
            pending = None
            tw.stop()
        if time.perf_counter() - t0 >= seconds:
            break
    if pending is not None:
        losses.append(float(pending))
    return {"steps": n, "losses": losses,
            "seconds": time.perf_counter() - t0}


# ---------------------------------------------------------------------------
# the reference and the comparison
# ---------------------------------------------------------------------------

def reference_readings(cell: harness.Cell, config: dict, seed: int, devs, *,
                       mode: str = "f32", fault: str | None = None) -> dict:
    """The reference's readings on the same weights and rows, on the first
    chip.  ``fault="half_batch"`` plants that fault of the program in the
    reference put in its place."""
    ref = cell.reference
    B, S = int(cell.params["batch"]), int(cell.mix["seq_len"])
    rows = [tok.rows(seed, TRAIN_STREAM, i, B, S, config["vocab_size"])
            for i in range(CHECK_STEPS)]
    if fault == "half_batch":
        rows = [r[: B // 2] for r in rows]
    batches = [jax.device_put(r, devs[0]) for r in rows]
    loss_fn = functools.partial(_ref_loss, ref=ref, config=config, mode=mode)
    make = functools.partial(weights.make, ref.shapes(config),
                             ref.init_rules(config), seed, device=devs[0])
    return ref_train.steps(loss_fn, make, batches, cell.mix["optimizer"])


def _ref_loss(p, tokens, *, ref, config, mode):
    return ref.loss(p, tokens, config, mode)


def compare(prog: dict, ref: dict) -> dict:
    """The numbers a cell may compare (its limits name those it does):
    loss_gap     largest |loss − reference loss| over the checked steps;
    loss0_gap    the same for the first step alone;
    grad_gap     the worst leaf's gap between its first-gradient norm and
                 the reference's, over max(that leaf's reference norm, the
                 median leaf's);
    grad_median_gap    the median leaf's gap, the same way;
    change_gap   the worst leaf's gap for the change after the checked
                 steps, over the leaves whose reference gradient is at
                 least a thousandth of the median leaf's (the others move
                 under Adam by round-off alone);
    change_median_gap  the median leaf's change gap, the same way."""
    raw_med = float(np.median(list(ref["raw_grad"].values())))
    moving = [k for k in ref["change"] if ref["raw_grad"][k] >= 1e-3 * raw_med]

    def gaps(a: dict, b: dict, keys) -> list:
        med = float(np.median([b[k] for k in keys]))
        return [abs(a[k] - b[k]) / max(b[k], med, 1e-30) for k in keys]

    g = gaps(prog["grad"], ref["grad"], list(ref["grad"]))
    c = gaps(prog["change"], ref["change"], moving)
    return {"loss_gap": max(abs(a - b) for a, b in
                            zip(prog["loss"], ref["loss"])),
            "loss0_gap": abs(prog["loss"][0] - ref["loss"][0]),
            "grad_gap": max(g), "grad_median_gap": float(np.median(g)),
            "change_gap": max(c), "change_median_gap": float(np.median(c))}


def worst_leaves(prog: dict, ref: dict) -> dict:
    """Which leaf sets grad_gap and change_gap (for the log)."""
    out = {}
    for key in ("grad", "change"):
        b = ref[key]
        med = float(np.median(list(b.values())))
        out[key] = max(b, key=lambda k: abs(prog[key][k] - b[k])
                       / max(b[k], med, 1e-30))
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run(ctx: dict) -> dict:
    cell: harness.Cell = ctx["cell"]
    config, seed, devs = ctx["config"], ctx["seed"], ctx["devices"]
    B, S = int(cell.params["batch"]), int(cell.mix["seq_len"])
    step_flops = cell.counts.train_flops(config, B, S)
    ref = cell.reference
    prog = build(cell, config, devs, fault=ctx.get("fault"))
    initial_state(prog, config, ref, seed)
    harness.log(f"built and initialised at "
                f"{time.perf_counter() - ctx['t_start']:.2f}s")
    readings = first_steps(prog, config, ref, seed)
    harness.log(f"first losses {readings['loss']} at "
                f"{time.perf_counter() - ctx['t_start']:.2f}s")
    warm = warm_up(prog, seed)
    harness.log(f"warm-up step seconds {warm}")
    hlo = []
    if ctx["trace_dir"] is not None:
        b = batch(prog, seed, prog.next_step)
        with prog.mesh:
            hlo.append(prog.step.lower(prog.params, prog.opt_state, b,
                                       np.int32(0)).compile().as_text())
    setup_s = time.perf_counter() - ctx["t_start"]
    tw = harness.TraceWindow(ctx["trace_dir"], ctx["trace_seconds"])
    ctx["compiles"].armed = True
    w = window(prog, seed, ctx["seconds"], tw)
    ctx["compiles"].armed = False
    device = harness.device_info(devs)
    device["window_compiles"] = ctx["compiles"].compiles
    device["window_cache_loads"] = ctx["compiles"].cache_loads
    harness.log(f"window: {w['steps']} steps in {w['seconds']:.3f}s; "
                f"compiles {device['window_compiles']}, cache loads "
                f"{device['window_cache_loads']} inside it")
    prog.params = prog.opt_state = None
    del prog
    gc.collect()
    t_ref = time.perf_counter()
    ref_r = reference_readings(cell, config, seed, devs)
    harness.log(f"reference: {time.perf_counter() - t_ref:.2f}s, losses "
                f"{ref_r['loss']}")
    values = compare(readings, ref_r)
    harness.log(f"worst leaves {worst_leaves(readings, ref_r)}")
    finite = all(math.isfinite(x) for x in w["losses"])
    ok, checks = harness.judge(values, cell.params.get("limits"))
    checks["window_losses_finite"] = {"value": float(not finite),
                                      "limit": 0.0}
    tokens = w["steps"] * B * S
    return {
        "correct": bool(ok) and finite,
        "attempted": w["steps"],
        "failed": sum(not math.isfinite(x) for x in w["losses"]),
        "end_to_end": {"setup_s": setup_s,
                       "train_tokens_per_s": tokens / w["seconds"]},
        "device": device,
        "checks": checks,
        "hlo": hlo,
        "layer_ctx": {"step_flops": step_flops, "batch": B, "seq": S},
    }
