"""Whale Engine: strategy → execution plan → jitted step functions.

The engine is the paper's third layer (Fig 1): it consumes either (a) a
TaskGraph recorded by strategy scopes (Cases 1–5) or (b) an explicit
:class:`StrategySpec`, and produces an :class:`ExecutionPlan` whose methods
build the jitted training / serving step functions with full GSPMD
shardings.  The three planner steps from the paper map as:

  1. "Partition the model to Subgraphs"       → the TaskGraph / LMCfg stack
  2. "Map operator placements from the virtual device into the physical
     device"                                  → ShardingRules (logical axis →
                                                mesh axis) + PartitionSpecs
  3. "Add collective communication primitives among different subgraphs"
                                              → delegated to the XLA SPMD
                                                partitioner; verified post-hoc
                                                by the roofline harness, and
                                                explicit (ppermute / psum) in
                                                the pipeline and compressed-DP
                                                paths

Cross-pod gradient compression: with ``compress_pod=True`` the step is
wrapped in a ``shard_map`` that is *manual* over the ``pod`` axis and auto
(GSPMD) over the rest — the cross-pod gradient reduction becomes an explicit
int8 quantize → psum → dequantize with error feedback
(:mod:`repro.optim.grad_compress`), cutting DCN bytes 4×.

Heterogeneous clusters (DESIGN.md §2): the physical mesh stays rectangular —
heterogeneity lives in the *placement*, not the mesh shape.  When
``compile_plan`` is given a mixed-hardware ``ClusterSpec`` (plus the
workload's ``WorkloadMeta``), the resulting :class:`ExecutionPlan` carries a
:class:`~repro.core.hetero.HeteroPlacement`: throughput-proportional batch
shares per device group (``placement.batch_slices()`` feeds the data
loader) and latency-equalized per-stage layer counts.  A homogeneous spec
produces a plan byte-identical to the spec-less path (regression-guarded).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.cost_model import StrategySpec
from repro.core.sharding import (ShardingRules, hybrid_rules, make_mesh,
                                 use_rules)
from repro.core.vdevice import Cluster


# ---------------------------------------------------------------------------
# strategy → mesh / rules
# ---------------------------------------------------------------------------

def mesh_for_strategy(strat: StrategySpec, *, devices=None,
                      pods: int = 1, cluster_spec=None) -> Mesh:
    """Build a mesh whose axes realise the strategy.

    Axis order (major→minor): pod, stage, data, model — so TP rides the
    ICI-contiguous minor axis and only DP crosses pods.

    ``cluster_spec`` (a :class:`~repro.core.cost_model.ClusterSpec`) is
    validated against the strategy: shards must tile each hardware group
    without straddling a group boundary (DESIGN.md §2).  The mesh shape
    itself is unaffected — for a homogeneous spec the returned mesh is
    identical to the spec-less call; uneven *work* splits ride the
    placement (see :func:`compile_plan`), never the mesh.
    """
    if cluster_spec is not None:
        from repro.core.hetero import strategy_fits_cluster
        if not strategy_fits_cluster(strat, cluster_spec):
            raise ValueError(
                f"{strat.describe()} does not tile the device groups "
                f"{[(g.name, g.n_devices) for g in cluster_spec.groups]}")
    shape, names = [], []
    if pods > 1:
        shape.append(pods)
        names.append("pod")
    if strat.pp > 1:
        shape.append(strat.pp)
        names.append("stage")
    shape.append(strat.dp // pods if pods > 1 else strat.dp)
    names.append("data")
    shape.append(strat.model_parallel)   # tp and nested ep share the axis
    names.append("model")
    return make_mesh(shape, names, devices=devices)


def rules_for_strategy(mesh: Mesh, strat: StrategySpec) -> ShardingRules:
    rules = hybrid_rules(mesh, fsdp=strat.zero >= 3)
    if not strat.vocab_split:
        rules.rules["vocab"] = None
    return rules


def strategy_from_taskgraph(cluster: Cluster) -> StrategySpec:
    """Derive the StrategySpec implied by recorded scope annotations
    (the Cases-1..5 path: scopes → IR → engine)."""
    mesh = cluster.mesh
    tg = cluster.taskgraph
    kinds = set()
    micro = 1
    n_stages = 0
    dense_split = expert_split = False
    for sg in (tg.nodes if tg else []):
        for ann in sg.strategy:
            kinds.add(ann.kind)
            if ann.kind == "pipeline":
                micro = max(micro, ann.options.get("micro_batch", 1))
            if ann.kind == "stage":
                n_stages = max(n_stages, ann.options.get("index", 0) + 1)
            if ann.kind == "split":
                if ann.options.get("experts"):
                    expert_split = True
                else:
                    dense_split = True
    dp = 1
    for a in ("pod", "data"):
        if a in mesh.shape:
            dp *= mesh.shape[a]
    model_ax = mesh.shape.get("model", 1)
    tp = model_ax if dense_split else 1
    ep = model_ax if expert_split else 1
    pp = mesh.shape.get("stage", 1) if kinds & {"stage", "pipeline"} else 1
    return StrategySpec(dp=dp, tp=tp, pp=pp, ep=ep, micro_batches=micro,
                        vocab_split=dense_split)


# ---------------------------------------------------------------------------
# the plan
# ---------------------------------------------------------------------------

def _ns(mesh, tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda t: isinstance(t, P))


def _is_axes(t) -> bool:
    return isinstance(t, tuple) and all(isinstance(e, (str, type(None)))
                                        for e in t)


@dataclasses.dataclass
class ExecutionPlan:
    """Everything needed to build jitted steps for one (model, mesh, strategy).

    ``placement`` is populated only for mixed-hardware clusters: a
    :class:`~repro.core.hetero.HeteroPlacement` holding per-group batch
    shares and per-stage layer counts (None on homogeneous clusters, so
    the plan is byte-identical to the pre-heterogeneous planner).
    """
    model: Any                      # repro.models.lm.Model
    mesh: Mesh
    rules: ShardingRules
    strategy: StrategySpec
    placement: Any = None           # hetero.HeteroPlacement | None
    # per-DeviceGroup fused-kernel tile geometry ({group name → KernelTiles},
    # from repro.kernels.autotune): populated whenever the plan was compiled
    # against a ClusterSpec, so a V100 group and a P100 group in one job run
    # the same kernels with different block sizes.  None → library defaults.
    kernel_tiles: dict | None = None

    def __post_init__(self):
        self.param_axes = self.model.axes()
        self.param_shapes = self.model.param_shapes()
        fsdp = self.strategy.zero >= 3
        self.param_specs = self.rules.param_specs_tree(
            self.param_axes, self.param_shapes, fsdp=fsdp)
        self.param_shardings = _ns(self.mesh, self.param_specs)

    def tiles_for(self, group: str | None = None):
        """Autotuned :class:`~repro.kernels.autotune.KernelTiles` for one
        device group (or, with ``group=None``, the *smallest* tiling across
        groups — the safe choice for a single SPMD program that every part
        must be able to run)."""
        from repro.kernels.autotune import DEFAULT_TILES
        if not self.kernel_tiles:
            return DEFAULT_TILES
        if group is not None:
            return self.kernel_tiles.get(group, DEFAULT_TILES)
        tiles = list(self.kernel_tiles.values())
        lo = tiles[0]
        for t in tiles[1:]:
            lo = dataclasses.replace(
                lo, **{f.name: min(getattr(lo, f.name), getattr(t, f.name))
                       for f in dataclasses.fields(lo)})
        return lo

    # ---- shardings for aux trees ----
    def batch_specs(self, batch_tree):
        return jax.tree.map(
            lambda s: self.rules.spec_for(
                ("batch",) + (None,) * (len(s.shape) - 1), s.shape),
            batch_tree)

    def batch_shardings(self, batch_tree):
        return _ns(self.mesh, self.batch_specs(batch_tree))

    def opt_specs(self, optimizer):
        state_axes = optimizer.state_axes(self.param_axes)
        state_shapes = jax.eval_shape(optimizer.init, self.param_shapes)
        fsdp = self.strategy.zero >= 1
        return self.rules.param_specs_tree(state_axes, state_shapes, fsdp=fsdp)

    def state_specs(self, batch: int, cache_len: int):
        shapes = self.model.decode_state_shapes(batch, cache_len)
        axes = self.model.state_axes()
        return jax.tree.map(
            lambda names, sds: self.rules.spec_for(names, sds.shape),
            axes, shapes, is_leaf=_is_axes)

    # ---- init ----
    def init_params(self, key):
        """Initialise params directly into their shardings (no host gather)."""
        with self.mesh:
            return jax.jit(self.model.init,
                           out_shardings=self.param_shardings)(key)

    # ---- training ----
    def train_step_fn(self, optimizer, *, micro_batches: int | None = None,
                      compress_pod: bool = False,
                      shard_grads: bool = False) -> Callable:
        """(params, opt_state, batch, step) → (params, opt_state, metrics).

        Unjitted body; use :meth:`jit_train_step` for the compiled version.
        ``micro_batches`` > 1 runs sequential gradient accumulation (the
        GPipe-style micro-batching of Case 4 without the stage axis; the
        staged pipeline lives in :mod:`repro.core.pipeline`).
        ``shard_grads``: constrain accumulated gradients to the parameter
        shardings so the DP reduction lowers to reduce-scatter (ZeRO) rather
        than a full all-reduce followed by slicing.
        """
        model, rules = self.model, self.rules
        M = micro_batches or self.strategy.micro_batches or 1
        mesh = self.mesh
        gspecs = self.param_specs

        def constrain_grads(g):
            if not shard_grads:
                return g
            return jax.tree.map(
                lambda x, s: jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, s)),
                g, gspecs, is_leaf=lambda t: isinstance(t, P))

        def grads_of(params, batch):
            (loss, metrics), g = jax.value_and_grad(
                model.loss_fn, has_aux=True)(params, batch)
            return constrain_grads(g), loss, metrics

        def accumulate(params, batch):
            if M <= 1:
                g, loss, metrics = grads_of(params, batch)
                return g, loss, metrics

            def to_micro(x):
                from repro.core.pipeline import check_micro_divides
                check_micro_divides(x.shape[0], M)
                return x.reshape((M, x.shape[0] // M) + x.shape[1:])

            split = jax.tree.map(to_micro, batch)

            def body(carry, mb):
                acc, loss_sum = carry
                g, loss, metrics = grads_of(params, mb)
                return (jax.tree.map(jnp.add, acc, g), loss_sum + loss), metrics

            zeros = constrain_grads(jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params))
            (g, loss_sum), metrics = jax.lax.scan(
                body, (zeros, jnp.zeros((), jnp.float32)), split)
            g = jax.tree.map(lambda a: a / M, g)
            metrics = jax.tree.map(lambda a: a.mean(0), metrics)
            return g, loss_sum / M, metrics

        if compress_pod and "pod" in self.mesh.shape:
            from repro.optim import grad_compress

            def step_fn(params, opt_state, batch, step, comp_err):
                with use_rules(rules):
                    g, loss, metrics = accumulate(params, batch)
                    # cross-pod reduction with int8 error feedback (explicit;
                    # the in-pod reduction already happened under GSPMD)
                    g, comp_err = grad_compress.compressed_psum_tree(
                        g, "pod", comp_err, mean=True)
                    new_params, new_opt = optimizer.apply(
                        g, opt_state, params, step)
                metrics = dict(metrics, loss=loss)
                metrics = jax.tree.map(
                    lambda m: jax.lax.pmean(m, "pod"), metrics)
                return new_params, new_opt, metrics, comp_err

            return step_fn

        def step_fn(params, opt_state, batch, step):
            with use_rules(rules):
                g, loss, metrics = accumulate(params, batch)
                new_params, new_opt = optimizer.apply(
                    g, opt_state, params, step)
            metrics = dict(metrics, loss=loss)
            return new_params, new_opt, metrics

        return step_fn

    def jit_train_step(self, optimizer, batch_tree, *,
                       micro_batches: int | None = None,
                       compress_pod: bool = False, donate: bool = True,
                       shard_grads: bool = False):
        """Jitted train step with full in/out shardings."""
        fn = self.train_step_fn(optimizer, micro_batches=micro_batches,
                                compress_pod=compress_pod,
                                shard_grads=shard_grads)
        mesh = self.mesh
        pspec = self.param_shardings
        ospec = _ns(mesh, self.opt_specs(optimizer))
        bspec = self.batch_shardings(batch_tree)
        rep = NamedSharding(mesh, P())
        if compress_pod and "pod" in mesh.shape:
            # manual over 'pod' only: GSPMD still partitions data/model inside
            inner = jax.shard_map(
                fn, mesh=mesh,
                in_specs=(P(), P(), P("pod"), P(), P()),
                out_specs=(P(), P(), P(), P()),
                axis_names=frozenset({"pod"}), check_vma=False)
            in_sh = (pspec, ospec, bspec, rep, pspec)
            jfn = jax.jit(inner, in_shardings=in_sh,
                          out_shardings=(pspec, ospec, rep, pspec),
                          donate_argnums=(0, 1, 4) if donate else ())
            return jfn
        in_sh = (pspec, ospec, bspec, rep)
        return jax.jit(fn, in_shardings=in_sh,
                       out_shardings=(pspec, ospec, rep),
                       donate_argnums=(0, 1) if donate else ())

    # ---- pipelined training (pp > 1; the schedule subsystem) ----
    def stage_layers(self):
        """Per-stage pattern-repeat counts for this plan's pipeline.

        Uneven when the plan carries a balanced :class:`HeteroPlacement`
        (its latency-equalizing ``layer_alloc``), else the even split.
        """
        import repro.core.pipeline as pipe
        S = self.strategy.pp
        if self.model.stack is None:
            # encdec: the pipeline cut is the fixed encoder|decoder tower
            # edge, not a layer-count split (see make_encdec_pipeline_loss)
            ecfg = self.model.ecfg
            return (ecfg.n_enc_layers, ecfg.n_dec_layers)
        if self.placement is not None and len(
                self.placement.layer_alloc) == S:
            return pipe.stage_layers_from_alloc(
                self.model.stack, self.placement.layer_alloc)
        return pipe.even_stage_layers(self.model.stack.n_rep, S)

    def jit_pipeline_train_step(self, optimizer, *,
                                micro_batches: int | None = None,
                                schedule: str | None = None,
                                stage_layers=None,
                                donate: bool = True):
        """Jitted (params, opt_state, tokens, step) → (params, opt_state,
        loss) through the pipeline executor (paper Cases 3–4).

        Requires a ``stage`` mesh axis (``mesh_for_strategy`` adds one for
        ``pp > 1`` plans).  Stage layer counts come from
        :meth:`stage_layers` — a heterogeneous plan's uneven allocation
        executes as-is — and the schedule defaults to the plan's
        ``strategy.schedule``.  Params/optimizer state use the padded
        stage-sharded layout of ``pipeline_params`` (identity for even
        splits).
        """
        import repro.core.pipeline as pipe
        if self.strategy.pp <= 1 or "stage" not in self.mesh.shape:
            raise ValueError(
                f"pipeline step needs pp > 1 and a 'stage' mesh axis; "
                f"strategy is {self.strategy.describe()}, mesh axes "
                f"{tuple(self.mesh.shape)}")
        if self.model.stack is None:
            # encdec routes to the two-tower engine: stage 0 = frontend +
            # encoder, stage 1 = decoder + loss head; stage_layers/schedule
            # do not apply (the cut is the fixed tower edge)
            return pipe.make_encdec_pipeline_train_step(
                self.model, self.mesh, self.rules, optimizer,
                micro_batches=micro_batches
                or self.strategy.micro_batches or 1,
                donate=donate)
        return pipe.make_pipeline_train_step(
            self.model, self.mesh, self.rules, optimizer,
            micro_batches=micro_batches or self.strategy.micro_batches or 1,
            stage_layers=stage_layers or self.stage_layers(),
            schedule=schedule or self.strategy.schedule,
            donate=donate)

    def init_pipeline_params(self, key, *, stage_layers=None):
        """Initialise params directly into the pipeline's (possibly
        padded) stage-sharded layout."""
        import repro.core.pipeline as pipe
        if self.model.stack is None:
            # encdec pipeline params are stage-replicated standard layout
            with self.mesh:
                return jax.jit(self.model.init,
                               out_shardings=self.param_shardings)(key)
        sl = stage_layers or self.stage_layers()
        pspecs = pipe.staged_specs(self.rules, self.param_axes,
                                   pipe._padded_model_shapes(self.model, sl))
        psh = _ns(self.mesh, pspecs)
        with self.mesh:
            return jax.jit(
                lambda k: pipe.pipeline_params(self.model,
                                               self.model.init(k), sl),
                out_shardings=psh)(key)

    # ---- serving ----
    def jit_serve_step(self, batch: int, cache_len: int, donate: bool = True):
        model, rules, mesh = self.model, self.rules, self.mesh

        def serve(params, tokens, state):
            with use_rules(rules):
                return model.serve_step(params, tokens, state)

        sspec = _ns(mesh, self.state_specs(batch, cache_len))
        tok = NamedSharding(mesh, self.rules.spec_for(("batch",), (batch,)))
        logits_sh = NamedSharding(
            mesh, self.rules.spec_for(("batch", "vocab"),
                                      (batch, self.model.cfg.padded_vocab)))
        return jax.jit(serve,
                       in_shardings=(self.param_shardings, tok, sspec),
                       out_shardings=(logits_sh, sspec),
                       donate_argnums=(2,) if donate else ())

    def jit_prefill(self, batch_tree, gen_budget: int = 64):
        model, rules, mesh = self.model, self.rules, self.mesh

        def prefill(params, batch):
            with use_rules(rules):
                return model.prefill(params, batch, gen_budget=gen_budget)

        bspec = self.batch_shardings(batch_tree)
        return jax.jit(prefill, in_shardings=(self.param_shardings, bspec))

    def paged_state_specs(self, batch: int, n_pages: int, page_size: int,
                          max_pages: int):
        shapes = self.model.paged_state_shapes(batch, n_pages, page_size,
                                               max_pages)
        axes = self.model.paged_state_axes()
        return jax.tree.map(
            lambda names, sds: self.rules.spec_for(names, sds.shape),
            axes, shapes, is_leaf=_is_axes)

    def jit_serve_step_paged(self, batch: int, n_pages: int, page_size: int,
                             max_pages: int, donate: bool = True):
        model, rules, mesh = self.model, self.rules, self.mesh

        def serve(params, tokens, state):
            with use_rules(rules):
                return model.serve_step_paged(params, tokens, state)

        sspec = _ns(mesh, self.paged_state_specs(batch, n_pages, page_size,
                                                 max_pages))
        tok = NamedSharding(mesh, self.rules.spec_for(("batch",), (batch,)))
        logits_sh = NamedSharding(
            mesh, self.rules.spec_for(("batch", "vocab"),
                                      (batch, self.model.cfg.padded_vocab)))
        return jax.jit(serve,
                       in_shardings=(self.param_shardings, tok, sspec),
                       out_shardings=(logits_sh, sspec),
                       donate_argnums=(2,) if donate else ())

    # ---- loss only (benchmarks / eval) ----
    def jit_loss(self, batch_tree):
        model, rules, mesh = self.model, self.rules, self.mesh

        def loss(params, batch):
            with use_rules(rules):
                return model.loss_fn(params, batch)

        return jax.jit(loss, in_shardings=(self.param_shardings,
                                           self.batch_shardings(batch_tree)))


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def compile_plan(model, mesh: Mesh, strategy: StrategySpec | None = None,
                 rules: ShardingRules | None = None, *,
                 cluster_spec=None, workload_meta=None, placement=None,
                 overlap: float = 0.0) -> ExecutionPlan:
    """The Whale Engine entry: model + mesh + strategy → ExecutionPlan.

    ``cluster_spec`` + ``workload_meta``: on a mixed-hardware cluster the
    plan additionally carries the balanced :class:`HeteroPlacement`
    (DESIGN.md §2) — per-group batch shares / per-stage layer counts,
    priced at ``overlap``.  A caller that already holds a placement (e.g.
    from the auto-search) passes it via ``placement`` and no re-balancing
    happens.  A homogeneous (or absent) spec leaves ``plan.placement`` as
    None and the plan is identical to the pre-heterogeneous planner.
    """
    if strategy is None:
        dp = 1
        for a in ("pod", "data"):
            if a in mesh.shape:
                dp *= mesh.shape[a]
        strategy = StrategySpec(dp=dp, tp=mesh.shape.get("model", 1),
                                pp=mesh.shape.get("stage", 1))
    if rules is None:
        rules = rules_for_strategy(mesh, strategy)
    if (placement is None and cluster_spec is not None
            and not cluster_spec.is_homogeneous and workload_meta is not None):
        from repro.core.hetero import plan_placement
        placement = plan_placement(workload_meta, strategy, cluster_spec,
                                   overlap=overlap)
    kernel_tiles = None
    if cluster_spec is not None:
        from repro.kernels.autotune import autotune_cluster
        cfg = getattr(model, "cfg", None)
        if cfg is not None and getattr(cfg, "n_heads", 0):
            kernel_tiles = autotune_cluster(
                cluster_spec, head_dim=cfg.hd,
                group=cfg.n_heads // max(cfg.n_kv_heads, 1),
                d_model=cfg.d_model, vocab=cfg.padded_vocab)
    return ExecutionPlan(model=model, mesh=mesh, rules=rules,
                         strategy=strategy, placement=placement,
                         kernel_tiles=kernel_tiles)


def compile_plan_from_cluster(cluster: Cluster, model,
                              workload_meta=None) -> ExecutionPlan:
    """Cases-1..5 path: strategy inferred from the recorded TaskGraph.

    On a mixed-hardware cluster, pass the workload's ``WorkloadMeta``
    (e.g. ``graph_from_taskgraph(tg, batch).workload_meta()`` from
    :mod:`repro.core.auto`) to get a balanced placement on the plan;
    without it — or with a homogeneous ``cluster.spec`` —
    ``plan.placement`` stays None.
    """
    strat = strategy_from_taskgraph(cluster)
    return compile_plan(model, cluster.mesh, strategy=strat,
                        cluster_spec=getattr(cluster, "spec", None),
                        workload_meta=workload_meta)
