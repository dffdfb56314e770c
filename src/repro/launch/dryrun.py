import os
# a CPU tool: 512 virtual host devices stand in for the pod, and no chip
# on the machine is touched
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds the production ExecutionPlan (hybrid strategy:
DP(+ZeRO-3) over data axes × operator split over the model axis — the
paper's Case 2 generalised), lowers the real step function (train step incl.
optimizer update / prefill / serve step) against ShapeDtypeStruct inputs (no
allocation), compiles it for the 16×16 = 256-chip pod or the 2×16×16 =
512-chip multi-pod mesh, and extracts:

- ``memory_analysis()``     → bytes/device (proves the cell fits HBM)
- ``cost_analysis()``       → per-device HLO FLOPs + HBM bytes
- the post-SPMD HLO text    → per-collective byte volumes (the roofline's
                              collective term; see ``collective_bytes``)

Results append to a JSONL file consumed by ``benchmarks/roofline.py`` and
EXPERIMENTS.md.  Any failure here (sharding mismatch, OOM at compile,
unsupported collective) is a bug in the system, not in the harness.

Usage::

    python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--jobs N]
"""
import argparse
import dataclasses
import json
import subprocess
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_NAMES, get_config
from repro.configs import shapes as sh
from repro.core.cost_model import TPU_V5E, StrategySpec
from repro.core.ir import jaxpr_flops
from repro.core.planner import compile_plan
from repro.core.sharding import make_mesh
from repro.launch.hlo_analysis import collective_bytes, hbm_traffic_bytes
from repro.launch.mesh import make_production_mesh
from repro.models.lm import build, param_count
from repro.optim.optimizer import adamw

DEFAULT_OUT = "bench_out/dryrun.jsonl"


# ---------------------------------------------------------------------------
# one cell
# ---------------------------------------------------------------------------

def production_strategy(mesh, *, micro_batches: int = 8,
                        zero: int = 3,
                        schedule: str = "gpipe") -> StrategySpec:
    dp = 1
    for a in ("pod", "data"):
        if a in mesh.shape:
            dp *= mesh.shape[a]
    return StrategySpec(dp=dp, tp=mesh.shape.get("model", 1),
                        pp=mesh.shape.get("stage", 1),
                        micro_batches=micro_batches, zero=zero,
                        vocab_split=True, schedule=schedule)


# per-arch production train settings: the ≥50B-param archs need factored
# second moments + deeper micro-batching to fit 16 GB HBM (DESIGN.md §5)
TRAIN_OVERRIDES = {
    "grok-1-314b": dict(optimizer="adafactor", micro_batches=16),
    "jamba-v0.1-52b": dict(optimizer="adafactor", micro_batches=16),
}


def model_flops_for_cell(cfg, model, cell) -> float:
    """MODEL_FLOPS: 6·N·D train (N = active params), 2·N·D decode/prefill."""
    n_active = _active_params(cfg, model)
    if cell.step == "train":
        return 6.0 * n_active * cell.global_batch * cell.seq_len
    if cell.step == "prefill":
        return 2.0 * n_active * cell.global_batch * cell.seq_len
    return 2.0 * n_active * cell.global_batch          # one token / seq


def _active_params(cfg, model) -> float:
    n = param_count(model.param_shapes())
    if cfg.n_experts and cfg.top_k:
        # subtract the inactive routed-expert fraction
        F, E = cfg.d_ff_expert, cfg.d_model
        per_expert = 3 * E * F
        if cfg.family == "moe":
            n_moe_layers = cfg.n_layers // cfg.moe_every
        else:                                  # hybrid: MoE every other layer
            n_moe_layers = cfg.n_layers // 2
        inactive = n_moe_layers * (cfg.n_experts - cfg.top_k) * per_expert
        n -= inactive
    return float(n)


def model_min_bytes_for_cell(cfg, model, cell, *, micro_batches: int,
                             state_bytes: float = 0.0) -> float:
    """Analytic minimum HBM traffic (global, all devices) — the memory-
    roofline floor the achieved memory term is compared against.

    train:   weights streamed bf16 fwd+bwd+remat per micro-batch, optimizer
             f32 read+write + bf16 moments, activations r+w ×3 passes
    prefill: weights once (bf16), activations r+w, KV write
    decode:  weights once (bf16), full decode state read + write
    """
    P = param_count(model.param_shapes())
    L = max(cfg.n_layers, 1) if cfg.family != "encdec" else (
        cfg.n_enc_layers + cfg.n_dec_layers)
    T = cell.global_batch * cell.seq_len
    E = cfg.d_model
    if cell.step == "train":
        weights = 3.0 * micro_batches * P * 2
        opt = P * (4 + 4 + 4 + 2 * 4)          # f32 r+w, grads, moments
        acts = 6.0 * L * T * E * 2
        return weights + opt + acts
    if cell.step == "prefill":
        return 2.0 * P + 4.0 * L * T * E * 2 + state_bytes
    # decode: one token per sequence
    return 2.0 * P + 2.0 * state_bytes + 4.0 * L * cell.global_batch * E * 2


def _bf16_shapes(tree):
    """Serving-dtype parameter stand-ins (bf16 checkpoints — production)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, jnp.bfloat16 if s.dtype == jnp.float32 else s.dtype), tree)


def run_cell(arch: str, shape: str, *, multi_pod: bool = False,
             micro_batches: int = 8, overrides: dict | None = None,
             strategy: StrategySpec | None = None,
             optimizer: str | None = None,
             context_parallel: bool = False,
             shard_grads: bool = False,
             mesh_shape: tuple | None = None,
             schedule: str = "gpipe",
             tag: str = "") -> dict:
    t_start = time.time()
    if mesh_shape is not None:               # perf-iteration mesh override
        names = ("pod", "data", "model")[-len(mesh_shape):]
        mesh = make_mesh(mesh_shape, names)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    model = build(cfg)
    cell = sh.SHAPES[shape]
    rec = {"arch": arch, "shape": shape, "mesh": "x".join(
        str(s) for s in mesh.devices.shape), "multi_pod": multi_pod,
        "step": cell.step, "tag": tag}

    ok, reason = sh.applicable(cfg, shape)
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec

    tov = TRAIN_OVERRIDES.get(arch, {}) if cell.step == "train" else {}
    micro = micro_batches if micro_batches != 8 else \
        tov.get("micro_batches", micro_batches)
    opt_name = optimizer or tov.get("optimizer", "adamw")
    if cell.step == "train":
        # per-micro-batch global batch must still divide over the dp shards
        dp_sz = 1
        for a in ("pod", "data"):
            dp_sz *= mesh.shape.get(a, 1)
        while micro > 1 and cell.global_batch % (micro * dp_sz):
            micro //= 2
    strat = strategy or production_strategy(mesh, micro_batches=micro,
                                            schedule=schedule)
    rec["schedule"] = strat.schedule
    from repro.core.sharding import hybrid_rules
    rules = hybrid_rules(mesh, fsdp=strat.zero >= 3,
                         context_parallel=context_parallel)
    if not strat.vocab_split:
        rules.rules["vocab"] = None
    plan = compile_plan(model, mesh, strategy=strat, rules=rules)

    state_bytes = 0.0
    with mesh:
        if cell.step == "train":
            if opt_name == "adafactor":
                from repro.optim.optimizer import adafactor
                opt = adafactor(lr=1e-4)
            else:
                opt = adamw(lr=1e-4, moment_dtype="bfloat16")
            bspecs = sh.batch_specs(model, cell)
            fn = plan.jit_train_step(opt, bspecs,
                                     micro_batches=strat.micro_batches,
                                     shard_grads=shard_grads)
            oshapes = jax.eval_shape(opt.init, plan.param_shapes)
            args = (plan.param_shapes, oshapes, bspecs,
                    jax.ShapeDtypeStruct((), jnp.int32))
            flop_fn = plan.train_step_fn(opt,
                                         micro_batches=strat.micro_batches)
        elif cell.step == "prefill":
            bspecs = sh.batch_specs(model, cell)
            fn = plan.jit_prefill(bspecs, gen_budget=0)
            args = (_bf16_shapes(plan.param_shapes), bspecs)
            flop_fn = lambda p, b: model.prefill(p, b, gen_budget=0)
        else:                                   # decode
            specs = sh.decode_specs(model, cell)
            fn = plan.jit_serve_step(cell.global_batch, cell.seq_len,
                                     donate=True)
            args = (_bf16_shapes(plan.param_shapes), specs["tokens"],
                    specs["state"])
            flop_fn = model.serve_step
            state_bytes = sum(
                s.size * s.dtype.itemsize
                for s in jax.tree.leaves(specs["state"]))
        t0 = time.time()
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        # trip-count-exact logical FLOPs (jaxpr walk; global shapes)
        flops_global = float(jaxpr_flops(jax.make_jaxpr(flop_fn)(*args).jaxpr))

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = collective_bytes(hlo, n_dev)
    hbm_dev = hbm_traffic_bytes(hlo)

    hw = TPU_V5E
    flops_dev = flops_global / n_dev
    t_comp = flops_dev / hw.peak_flops
    t_mem = hbm_dev / hw.hbm_bw
    t_coll = coll["total"] / hw.link_bw["fast"]
    mf = model_flops_for_cell(cfg, model, cell)
    min_bytes = model_min_bytes_for_cell(cfg, model, cell,
                                         micro_batches=strat.micro_batches,
                                         state_bytes=state_bytes)
    t_ideal = max(mf / n_dev / hw.peak_flops,
                  min_bytes / n_dev / hw.hbm_bw)

    rec.update(
        status="ok",
        strategy=strat.describe(),
        optimizer=opt_name if cell.step == "train" else None,
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        total_s=round(time.time() - t_start, 2),
        mem_args_gib=ma.argument_size_in_bytes / 2**30,
        mem_temp_gib=ma.temp_size_in_bytes / 2**30,
        mem_out_gib=ma.output_size_in_bytes / 2**30,
        flops_per_dev=flops_dev,
        hbm_bytes_per_dev=hbm_dev,
        cost_analysis_flops_raw=float(ca.get("flops", 0.0)),
        cost_analysis_bytes_raw=float(ca.get("bytes accessed", 0.0)),
        coll_bytes_per_dev=coll["total"],
        coll_detail={k: v for k, v in coll.items() if k != "counts"},
        coll_counts=coll["counts"],
        t_compute=t_comp, t_memory=t_mem, t_collective=t_coll,
        bottleneck=max([("compute", t_comp), ("memory", t_mem),
                        ("collective", t_coll)], key=lambda kv: kv[1])[0],
        model_flops=mf,
        model_min_bytes=min_bytes,
        model_flops_hlo_ratio=mf / max(flops_global, 1.0),
        t_ideal=t_ideal,
        roofline_frac=t_ideal / max(max(t_comp, t_mem, t_coll), 1e-30),
        hlo_len=len(hlo),
    )
    return rec


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _append(rec: dict, path: str) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "a") as f:
        f.write(json.dumps(rec) + "\n")


def _run_all(args) -> int:
    """Each cell in a fresh subprocess (isolates compile memory/failures)."""
    cells = [(a, s) for a in ARCH_NAMES for s in sh.SHAPES]
    failures = 0
    for arch, shape in cells:
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
               "--shape", shape, "--out", args.out,
               "--micro-batches", str(args.micro_batches)]
        if args.multi_pod:
            cmd.append("--multi-pod")
        t0 = time.time()
        p = subprocess.run(cmd, capture_output=True, text=True,
                           env=dict(os.environ, JAX_PLATFORMS="cpu"))
        dt = time.time() - t0
        if p.returncode:
            failures += 1
            _append({"arch": arch, "shape": shape,
                     "multi_pod": args.multi_pod, "status": "failed",
                     "error": p.stderr[-2000:]}, args.out)
            print(f"FAIL  {arch:22s} {shape:12s} ({dt:5.1f}s)")
            print(p.stderr[-800:])
        else:
            tail = p.stdout.strip().splitlines()
            print(f"ok    {arch:22s} {shape:12s} ({dt:5.1f}s)  "
                  f"{tail[-1] if tail else ''}")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=tuple(sh.SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--micro-batches", type=int, default=8)
    ap.add_argument("--out", default=DEFAULT_OUT)
    # --- perf-iteration knobs (EXPERIMENTS.md §Perf) ---
    ap.add_argument("--context-parallel", action="store_true",
                    help="shard q-seq over the model axis (heads∤tp archs)")
    ap.add_argument("--shard-grads", action="store_true",
                    help="constrain grads to param shardings (reduce-scatter)")
    ap.add_argument("--set", default="",
                    help="comma k=v LMCfg overrides (attn_bwd_remat=True,...)")
    ap.add_argument("--mesh-shape", default="",
                    help="override mesh, e.g. 32x8 (data×model) — perf knob")
    ap.add_argument("--no-vocab-split", action="store_true",
                    help="ablate the paper's Fig-4 split-classifier technique")
    ap.add_argument("--schedule", choices=("gpipe", "1f1b"), default="gpipe",
                    help="pipeline schedule recorded on the strategy and in "
                         "the JSONL (production meshes have no stage axis, "
                         "so it prices nothing until a pp>1 mesh is used; "
                         "repro.core.schedule)")
    ap.add_argument("--tag", default="", help="label for the JSONL record")
    args = ap.parse_args()

    if args.all:
        sys.exit(1 if _run_all(args) else 0)

    overrides = {}
    if args.set:
        from repro.configs import get_config as _gc
        ref = _gc(args.arch)
        for pair in args.set.split(","):
            k, v = pair.split("=")
            cur = getattr(ref, k)
            overrides[k] = (v == "True") if isinstance(cur, bool) else \
                type(cur)(v)

    mesh_shape = tuple(int(x) for x in args.mesh_shape.split("x")) \
        if args.mesh_shape else None
    strategy = None
    if args.no_vocab_split:
        base = (make_mesh(mesh_shape,
                          ("pod", "data", "model")[-len(mesh_shape):])
                if mesh_shape else make_production_mesh(
                    multi_pod=args.multi_pod))
        strategy = dataclasses.replace(
            production_strategy(base, micro_batches=args.micro_batches,
                                schedule=args.schedule),
            vocab_split=False)
    rec = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                   micro_batches=args.micro_batches, overrides=overrides,
                   context_parallel=args.context_parallel,
                   shard_grads=args.shard_grads, mesh_shape=mesh_shape,
                   schedule=args.schedule, strategy=strategy, tag=args.tag)
    _append(rec, args.out)
    if rec["status"] == "ok":
        print(f"{rec['arch']} {rec['shape']} mesh={rec['mesh']} "
              f"temp={rec['mem_temp_gib']:.2f}GiB "
              f"args={rec['mem_args_gib']:.2f}GiB "
              f"compute={rec['t_compute']*1e3:.1f}ms "
              f"mem={rec['t_memory']*1e3:.1f}ms "
              f"coll={rec['t_collective']*1e3:.1f}ms "
              f"bott={rec['bottleneck']} rf={rec['roofline_frac']:.3f}")
    else:
        print(f"{rec['arch']} {rec['shape']}: {rec['status']} "
              f"({rec.get('reason', '')})")


if __name__ == "__main__":
    main()
