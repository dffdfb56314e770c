"""Fault-tolerant, self-healing training driver (CLI).

Composes the whole stack: config → model → Whale plan (manual or
auto-parallel) → data pipeline → jitted train step → fault-tolerant loop
with async checkpoints, straggler monitoring, and auto-resume.

The multi-host control loop lives in
:mod:`repro.runtime.controller` — the event-driven membership runtime
(DESIGN.md §12) that closes Whale's resource-adaptability loop in both
directions: sustained stragglers and spot-reclaimed hosts are **evicted**
and the job rebalances onto the survivors; joining hosts are **admitted**
and the job rebalances onto the grown fleet.  ``TrainController`` is kept
here as a thin alias of
:class:`~repro.runtime.controller.ClusterController` for callers of the
old name.

Usage (CPU sanity run)::

    python -m repro.launch.train --arch tinyllama-1.1b --smoke \
        --steps 50 --batch 8 --seq 128 --mesh 1x1

Self-healing run with an injected straggler (4 virtual devices = 2
simulated hosts; host 1 goes 4× slower at step 6 and is evicted)::

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    python -m repro.launch.train --arch tinyllama-1.1b --smoke \
        --steps 20 --batch 8 --seq 64 --hosts 2 --inject-slow 1:6:4

Spot fleet: host 1 gets a reclaim warning at step 6 (2-step deadline) and
host 2 re-joins with 2 devices at step 14 (6 visible devices = 2 live
hosts × 2 devices + 2 spare for the join)::

    XLA_FLAGS=--xla_force_host_platform_device_count=6 \
    python -m repro.launch.train --arch tinyllama-1.1b --smoke \
        --steps 24 --batch 8 --seq 64 --hosts 2 --devices-per-host 2 \
        --inject-preempt 1:6:2 --inject-join 2:14:2

Multi-host TPU: every host runs the same command; ``--distributed`` calls
``jax.distributed.initialize()`` first (single-process here, exercised via
the simulated :class:`~repro.runtime.elastic.HostTopology` instead).
"""
from __future__ import annotations

import argparse
import dataclasses
import tempfile

import jax
import jax.numpy as jnp

from repro.ckpt.checkpoint import CheckpointManager
from repro.configs import ARCH_NAMES, get_config
from repro.core.auto import auto_parallel
from repro.core.cost_model import (HARDWARE_BY_NAME, StrategySpec,
                                   device_hardware, step_cost_features)
from repro.core.planner import compile_plan, mesh_for_strategy
from repro.core.sharding import make_mesh
from repro.data.pipeline import DataCfg, MultimodalPipeline, TokenPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.optim.optimizer import Schedule, adamw, adafactor
from repro.runtime.controller import (CalibrationConfig, ClusterController,
                                      ElasticConfig)
from repro.runtime.elastic import HostTopology
from repro.runtime.fault_tolerance import FaultTolerantLoop
from repro.runtime.faults import (FaultInjector, JoinHost, SlowHost,
                                  CrashStep, DriftHost, SpotPreemption)
from repro.runtime.profiler import Profiler
from repro.runtime.straggler import StragglerMonitor

# the old name, re-exported for existing callers/tests; the implementation
# moved to repro.runtime.controller
TrainController = ClusterController


def parse_mesh(spec: str):
    """``"4"`` → data 4; ``"4x2"`` → data 4 × model 2; ``"2x4x2"`` adds pod."""
    dims = tuple(int(x) for x in spec.split("x"))
    names = {1: ("data",), 2: ("data", "model"), 3: ("pod", "data", "model")}
    return make_mesh(dims, names[len(dims)])


def _parse_injections(slow: list, crash: list, drift: list = (),
                      preempt: list = (), join: list = ()) -> tuple:
    scenarios = []
    for s in slow or []:
        host, start, factor = s.split(":")
        scenarios.append(SlowHost(host=int(host), start_step=int(start),
                                  factor=float(factor)))
    for c in crash or []:
        bits = c.split(":")
        scenarios.append(CrashStep(step=int(bits[0]),
                                   times=int(bits[1]) if len(bits) > 1
                                   else 1))
    for d in drift or []:
        host, start, end, factor = d.split(":")
        scenarios.append(DriftHost(host=int(host), start_step=int(start),
                                   end_step=int(end), factor=float(factor)))
    for p in preempt or []:
        bits = p.split(":")
        scenarios.append(SpotPreemption(
            host=int(bits[0]), warn_step=int(bits[1]),
            deadline_steps=int(bits[2]) if len(bits) > 2 else 2))
    for j in join or []:
        host, step, n_dev = j.split(":")
        scenarios.append(JoinHost(host=int(host), step=int(step),
                                  n_devices=int(n_dev)))
    return tuple(scenarios)


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", "--model", dest="arch", choices=ARCH_NAMES,
                    default="tinyllama-1.1b",
                    help="architecture to train (--model is an alias; "
                         "includes the M6 multimodal workloads, e.g. "
                         "qwen2-vl-2b / seamless-m4t-medium)")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--src-seq", type=int, default=None,
                    help="encoder-side source length for encdec archs "
                         "(frames per sample); default: --seq")
    ap.add_argument("--mesh", default="", help="e.g. 4x2 = data4 × model2")
    ap.add_argument("--micro-batches", type=int, default=None,
                    help="default: the plan's choice (1 when unplanned)")
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline stages (adds a 'stage' mesh axis)")
    ap.add_argument("--schedule", choices=("gpipe", "1f1b"), default=None,
                    help="pipeline schedule (repro.core.schedule); "
                         "default: the plan's choice")
    ap.add_argument("--stage-layers", default="",
                    help="comma layer-repeats per stage (uneven pipelines, "
                         "e.g. 3,2,2,1); default even split")
    ap.add_argument("--optimizer", choices=("adamw", "adafactor"),
                    default="adamw")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--auto", action="store_true",
                    help="pick the strategy with the Whale cost model")
    ap.add_argument("--compress-pod", action="store_true")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory to save to and resume from; "
                         "default: a fresh temporary directory (no resume)")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--distributed", action="store_true")
    ap.add_argument("--overrides", default="",
                    help="comma k=v LMCfg overrides (e.g. n_layers=4)")
    # ---- fused-kernel selection (PR 6: training-grade pallas paths) ----
    ap.add_argument("--attn", choices=("ref", "pallas"), default=None,
                    help="attention impl: pallas = fused flash fwd+bwd "
                         "(interpret-mode off-TPU); default: config's choice")
    ap.add_argument("--xent", choices=("ref", "pallas"), default=None,
                    help="loss head impl: pallas = fused xent kernel")
    ap.add_argument("--hw", choices=sorted(HARDWARE_BY_NAME), default=None,
                    help="Hardware table the kernel-tile autotuner and the "
                         "--profile report target (repro.kernels.autotune); "
                         "default: the table of the device in use")
    # ---- self-healing elastic runtime (DESIGN.md §7) ----
    ap.add_argument("--hosts", type=int, default=0,
                    help="simulate N hosts over the visible devices and run "
                         "the self-healing TrainController (straggler "
                         "eviction + rebalance + resume)")
    ap.add_argument("--inject-slow", action="append", default=[],
                    metavar="HOST:STEP:FACTOR",
                    help="fault injection: HOST runs FACTOR× slower from "
                         "STEP (repeatable)")
    ap.add_argument("--inject-crash", action="append", default=[],
                    metavar="STEP[:TIMES]",
                    help="fault injection: transient step failure at STEP")
    # ---- cluster membership (DESIGN.md §12: spot fleets, scale-up) ----
    ap.add_argument("--inject-preempt", action="append", default=[],
                    metavar="HOST:WARN[:DEADLINE]",
                    help="spot reclaim: HOST is warned at step WARN and "
                         "vanishes DEADLINE steps later (default 2; 0 = "
                         "missed notice, falls back to the last committed "
                         "checkpoint) (repeatable)")
    ap.add_argument("--inject-join", action="append", default=[],
                    metavar="HOST:STEP:NDEV",
                    help="scale-up / spot re-admission: HOST offers NDEV "
                         "devices from STEP on (repeatable; needs spare "
                         "visible devices — see --devices-per-host)")
    ap.add_argument("--devices-per-host", type=int, default=0,
                    help="devices each simulated host owns (default: "
                         "device count / --hosts); set it below that to "
                         "leave spare devices for --inject-join")
    ap.add_argument("--patience", type=int, default=3)
    ap.add_argument("--straggler-warmup", type=int, default=3)
    ap.add_argument("--max-rebalances", type=int, default=2)
    # ---- profile-calibrated cost model (DESIGN.md §10) ----
    ap.add_argument("--profile", action="store_true",
                    help="record per-group step observations against the "
                         "cost model's features and print the fitted "
                         "calibration report at exit")
    ap.add_argument("--calibrate", action="store_true",
                    help="drift-triggered continuous rebalancing: compare "
                         "predicted vs measured step cost and rebalance "
                         "with the re-fitted ClusterSpec when skew exceeds "
                         "--drift-skew (needs --hosts)")
    ap.add_argument("--drift-skew", type=float, default=0.25,
                    help="relative skew that triggers recalibration")
    ap.add_argument("--drift-patience", type=int, default=5,
                    help="sustained skewed steps before recalibrating")
    ap.add_argument("--inject-drift", action="append", default=[],
                    metavar="HOST:START:END:FACTOR",
                    help="fault injection: HOST ramps linearly to FACTOR× "
                         "slower between START and END (repeatable)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.distributed:
        jax.distributed.initialize()
    hw = HARDWARE_BY_NAME[args.hw] if args.hw else device_hardware()

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.overrides:
        kv = {}
        for pair in args.overrides.split(","):
            k, v = pair.split("=")
            cur = getattr(cfg, k)
            kv[k] = type(cur)(v) if not isinstance(cur, bool) else v == "True"
        cfg = dataclasses.replace(cfg, **kv)
    if args.attn:
        cfg = dataclasses.replace(cfg, attn_impl=args.attn)
    if args.xent:
        cfg = dataclasses.replace(cfg, xent_impl=args.xent)
    if "pallas" in (cfg.attn_impl, cfg.xent_impl, cfg.ssd_impl):
        # size the kernel tiles for the target part (per-Hardware autotune);
        # mixed clusters get per-group tiles on the plan via compile_plan
        from repro.kernels.autotune import autotune
        tiles = autotune(
            hw, head_dim=cfg.hd if cfg.n_heads else cfg.ssd_headdim,
            group=cfg.n_heads // max(cfg.n_kv_heads, 1) or 1,
            d_model=cfg.d_model, vocab=cfg.padded_vocab, seq=args.seq)
        cfg = dataclasses.replace(
            cfg, attn_block_q=tiles.block_q, attn_block_k=tiles.block_k,
            xent_block_t=tiles.xent_block_t, xent_block_v=tiles.xent_block_v,
            ssd_chunk=(tiles.ssd_chunk if cfg.family in ("ssm", "hybrid")
                       else cfg.ssd_chunk))
        print(f"[autotune] {hw.name}: {tiles}")
    from repro.models.lm import build, param_count
    model = build(cfg)

    # ---- optimizer / data / checkpoint (shared by both paths) ----
    sched = Schedule(base_lr=args.lr, warmup=min(100, args.steps // 10 + 1),
                     decay_steps=args.steps)
    opt = (adamw(lr=sched) if args.optimizer == "adamw"
           else adafactor(lr=sched))
    dcfg = DataCfg(global_batch=args.batch, seq_len=args.seq,
                   vocab=cfg.vocab, seed=args.seed)
    src_seq = args.src_seq or args.seq
    if cfg.family in ("vlm", "encdec"):
        # multimodal archs consume a modality stream alongside the tokens:
        # patch embeddings for vlm, source frames for encdec
        data = MultimodalPipeline(
            dcfg, modality=cfg.family, d_model=cfg.d_model,
            frontend_len=cfg.frontend_len if cfg.family == "vlm" else 0,
            src_len=src_seq if cfg.family == "encdec" else 0)
    else:
        data = TokenPipeline(dcfg)
    ckpt = CheckpointManager(
        args.ckpt_dir or tempfile.mkdtemp(prefix="repro_ckpt_"), keep=2)

    # ---- self-healing controller path (simulated multi-host) ----
    if args.hosts > 1:
        n = len(jax.devices())
        if args.devices_per_host:
            if args.hosts * args.devices_per_host > n:
                raise SystemExit(
                    f"--hosts {args.hosts} × --devices-per-host "
                    f"{args.devices_per_host} exceeds the device count "
                    f"({n})")
            dph = args.devices_per_host
        else:
            if n % args.hosts:
                raise SystemExit(f"--hosts {args.hosts} must divide the "
                                 f"device count ({n})")
            dph = n // args.hosts
        topology = HostTopology.uniform(args.hosts, dph, hw)
        scenarios = _parse_injections(args.inject_slow, args.inject_crash,
                                      args.inject_drift,
                                      args.inject_preempt, args.inject_join)
        # nominal clock: injected scenarios play on a fully simulated
        # timeline, so detection is deterministic regardless of machine
        # load (a real deployment feeds measured per-host times instead)
        injector = (FaultInjector(scenarios=scenarios, n_hosts=args.hosts,
                                  seed=args.seed, nominal=0.05)
                    if scenarios else None)
        calibration = None
        if args.calibrate:
            calibration = CalibrationConfig(
                skew=args.drift_skew, patience=args.drift_patience,
                max_rebalances=args.max_rebalances)
        elif args.profile:
            # record + report only: never trigger a rebalance
            calibration = CalibrationConfig(max_rebalances=0)
        ctl = TrainController(
            model, cfg, opt, data, ckpt,
            elastic=ElasticConfig(topology=topology,
                                  patience=args.patience,
                                  warmup=args.straggler_warmup,
                                  max_rebalances=args.max_rebalances,
                                  calibration=calibration),
            batch=args.batch, seq=args.seq, save_every=args.save_every,
            injector=injector, log_every=args.log_every)
        out = ctl.run(args.steps, seed=args.seed)
        if args.profile:
            print(ctl.profiler.report(ctl.topology.cluster_spec()))
        evictions = [e for e in out["events"] if e["kind"] == "evict"]
        recals = [e for e in out["events"] if e["kind"] == "recalibrate"]
        joins = [e for e in out["events"] if e["kind"] == "join"]
        loss_str = (f", loss {out['losses'][0]:.4f} → {out['losses'][-1]:.4f}"
                    if out["losses"] else " (resumed already complete)")
        print(f"[done] step {out['final_step']} phase {out['phase']}, "
              f"{len(evictions)} eviction(s), "
              f"{len(recals)} recalibration(s), "
              f"{len(joins)} join(s){loss_str}")
        return {"final_step": out["final_step"], "losses": out["losses"],
                "events": out["events"], "phase": out["phase"]}

    # ---- mesh & strategy ----
    # the cost model can PRICE a pipelined vlm (the planner/fig10 use it),
    # but the executable layer-stack engine is token-only — it has no slot
    # for the vision frontend or the M-RoPE position tensor, so this
    # driver never routes vlm to pp > 1
    if args.auto:
        # the segment-aware graph lets the search respect frontend/encoder/
        # decoder boundaries when it enumerates pipeline splits
        graph = model.graph(args.batch, args.seq, src_seq=src_seq)
        search_kw = {"max_pp": 1} if cfg.family == "vlm" else {}
        strat = auto_parallel(graph, len(jax.devices()), hw, **search_kw)
        print(f"[auto] chose: {strat.describe()}")
        mesh = mesh_for_strategy(strat)
    elif args.pp > 1:
        if cfg.family == "vlm":
            raise SystemExit(
                "--pp does not apply to vlm archs yet: the executable "
                "pipeline engine cannot stage the vision frontend "
                "(train non-pipelined, e.g. --dp, instead)")
        n = len(jax.devices())
        if n < args.pp or n % args.pp:
            raise SystemExit(
                f"--pp {args.pp} needs a device count divisible by the "
                f"stage count; have {n} device(s)")
        strat = StrategySpec(dp=n // args.pp, pp=args.pp,
                             micro_batches=args.micro_batches or 1,
                             schedule=args.schedule or "gpipe")
        mesh = mesh_for_strategy(strat)
    else:
        mesh = parse_mesh(args.mesh or str(len(jax.devices())))
        strat = None
    plan = compile_plan(model, mesh, strategy=strat)
    pipelined = plan.strategy.pp > 1 and "stage" in mesh.shape
    if pipelined:
        print(f"[pipeline] {plan.strategy.pp} stages, schedule "
              f"{args.schedule or plan.strategy.schedule}, µb="
              f"{args.micro_batches or plan.strategy.micro_batches}, "
              f"stage_layers {args.stage_layers or 'even/plan'}")

    # ---- init or resume ----
    if pipelined:
        import repro.core.pipeline as pipe
        stage_layers = None
        if args.stage_layers:
            if model.stack is None:
                raise SystemExit("--stage-layers does not apply to encdec "
                                 "archs: the pipeline cut is the fixed "
                                 "encoder|decoder tower edge")
            stage_layers = tuple(int(x) for x in args.stage_layers.split(","))
            pipe.check_stage_layers(stage_layers, model.stack.n_rep,
                                    plan.strategy.pp)
        params = plan.init_pipeline_params(jax.random.key(args.seed),
                                           stage_layers=stage_layers)
        with mesh:
            opt_state = jax.jit(opt.init)(params)
    else:
        with mesh:
            params = plan.init_params(jax.random.key(args.seed))
            opt_state = jax.jit(opt.init)(params)
    start_step = 0
    resume = ckpt.restore_latest({"params": params, "opt": opt_state})
    if resume is not None:
        start_step, tree, extra = resume
        params, opt_state = tree["params"], tree["opt"]
        if "data" in extra:
            data.load_state_dict(extra["data"])
        print(f"[resume] from step {start_step}")

    # exactly-once data, same discipline as TrainController: batches are
    # fetched idempotently per step (a retried step replays the SAME batch)
    # and checkpoints record the position of the committed step — the jit
    # warm-up example below is the batch of start_step, not a burned draw
    fetched = {"step": start_step - 1, "batch": None, "before": None}

    def batch_for(i):
        if fetched["step"] != i:
            fetched["before"] = data.state_dict()
            fetched["batch"] = {k: jnp.asarray(v)
                                for k, v in data.next_batch().items()}
            fetched["step"] = i
        return fetched["batch"]

    def data_state_at(s):
        if s == fetched["step"] and fetched["before"] is not None:
            return dict(fetched["before"])     # save at the failed step
        return data.state_dict()

    batch0 = batch_for(start_step)
    with mesh:
        if pipelined:
            step_fn = plan.jit_pipeline_train_step(
                opt, micro_batches=args.micro_batches,
                schedule=args.schedule, stage_layers=stage_layers)
        else:
            step_fn = plan.jit_train_step(
                opt, batch0, micro_batches=args.micro_batches,
                compress_pod=args.compress_pod)

    n_params = param_count(params)
    print(f"[train] {cfg.name}: {n_params:,} params, mesh "
          f"{dict(mesh.shape)}, {args.steps} steps")

    monitor = StragglerMonitor()
    profiler = None
    if args.profile:
        # whole-step observations against the executed strategy's feature
        # vector on the --hw table; the exit report shows how far the
        # hand-written rates are from this machine's measured ones
        prof_meta = model.graph(args.batch, args.seq,
                                src_seq=src_seq).workload_meta()
        prof_feats = step_cost_features(prof_meta, plan.strategy, hw)
        profiler = Profiler()
    losses, step_seconds = [], []
    state0 = {"params": params, "opt": opt_state}
    if args.compress_pod and "pod" in mesh.shape:
        from repro.optim import grad_compress
        state0["err"] = grad_compress.init_error_tree(params)

    def one_step(i, st):
        batch = batch_for(i)
        with mesh:
            if pipelined and "frames" in batch:
                # encdec two-tower pipeline: encoder memory ships over the
                # stage wire, so the step consumes frames AND tokens
                p, o, loss = step_fn(st["params"], st["opt"],
                                     batch["frames"], batch["tokens"],
                                     jnp.asarray(i))
                new, m = {"params": p, "opt": o}, {"loss": loss}
            elif pipelined:
                p, o, loss = step_fn(st["params"], st["opt"],
                                     batch["tokens"], jnp.asarray(i))
                new, m = {"params": p, "opt": o}, {"loss": loss}
            elif "err" in st:
                p, o, m, e = step_fn(st["params"], st["opt"], batch,
                                     jnp.asarray(i), st["err"])
                new = {"params": p, "opt": o, "err": e}
            else:
                p, o, m = step_fn(st["params"], st["opt"], batch,
                                  jnp.asarray(i))
                new = {"params": p, "opt": o}
        losses.append(float(m["loss"]))
        if i % args.log_every == 0 or i == args.steps - 1:
            print(f"  step {i:5d}  loss {losses[-1]:.4f}")
        return new

    def on_step(i, st, dt):
        step_seconds.append(dt)
        if profiler is not None and i > start_step:
            profiler.record_step(hw.name, dt, prof_feats, step=i)
        if monitor.observe(dt):       # one-shot: True on the flag transition
            print(f"[straggler] flagged at step {i} "
                  f"(dt={dt:.3f}s vs mean {monitor.mean:.3f}s)")
            monitor.reset()           # keep training; eviction is external

    loop = FaultTolerantLoop(ckpt, save_every=args.save_every)
    final_step, state = loop.run(
        state=state0, step_fn=one_step, n_steps=args.steps,
        start_step=start_step,
        extra_fn=lambda st, s: {"data": data_state_at(s)},
        on_step=on_step)

    if profiler is not None:
        from repro.core.cost_model import ClusterSpec
        print(profiler.report(ClusterSpec.homogeneous(hw,
                                                      len(jax.devices()))))
    loss_str = (f", loss {losses[0]:.4f} → {losses[-1]:.4f}" if losses
                else " (resumed already complete)")
    print(f"[done] step {final_step}{loss_str}")
    return {"final_step": final_step, "losses": losses,
            "step_seconds": step_seconds, "state": state}


if __name__ == "__main__":
    main()
