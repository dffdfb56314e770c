"""Pipeline parallelism over a ``stage`` mesh axis (paper Cases 3–4).

TPU adaptation (DESIGN.md §5): Whale pipelines TF graph partitions with
host-side queues; on TPU the native mechanism is a collective pipeline —
stage parameters are sharded over a ``stage`` mesh axis inside a
``shard_map`` (manual over ``stage``, GSPMD-auto over ``data``/``model`` so
pipeline composes with DP and operator sharding, the paper's Case 4), and
micro-batch activations move stage-to-stage with ``jax.lax.ppermute``.

Two executors, one schedule subsystem (:mod:`repro.core.schedule`):

1. **Fused SPMD engine** (:func:`make_pipeline_loss` /
   :func:`make_pipeline_train_step`) — the forward walks GPipe's forward
   wave as a ``lax.scan`` over ticks; ``jax.grad`` differentiates straight
   through it (the transpose of ``ppermute`` is the reverse ``ppermute``),
   yielding the mirrored backward — i.e. exactly the ``gpipe`` tick table.
   Stages may hold **uneven** layer counts: params live in a padded
   ``(S·Lmax, …)`` stage-sharded layout and each stage applies only its
   first ``stage_layers[s]`` repeats (gated scan; pad slots contribute
   nothing and receive zero gradients).  This is what executes the
   heterogeneity planner's latency-equalizing ``HeteroPlacement``
   (DESIGN.md §2) end to end.

2. **Schedule interpreter** (:func:`schedule_grads`) — the order-faithful
   reference engine: walks any :class:`~repro.core.schedule.Schedule`
   tick table on one device, running each fwd slot and each bwd slot (via
   ``jax.vjp`` with stage-input recompute, i.e. remat at stage
   granularity) in exactly the scheduled order, with an audited
   activation buffer whose high-water mark must match
   ``Schedule.peak_in_flight`` — the harness the schedule-equivalence
   tests drive.

Encoder–decoder models pipeline over their natural two-tower cut instead
of a layer-count split: :func:`make_encdec_pipeline_loss` runs the
(frontend +) encoder tower on stage 0 and the decoder tower + loss head
on stage 1, shipping the ``(micro_batch, S_src, d_model)`` encoder
memory across the wire each tick.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import schedule as sched_mod
from repro.core.sharding import ShardingRules, use_rules
from repro.models import encdec as encdec_mod
from repro.models import frontends
from repro.models import layers, transformer as tfm
from repro.models.lm import Model, chunked_xent


def _is_axes(t) -> bool:
    return isinstance(t, tuple) and all(isinstance(e, (str, type(None)))
                                        for e in t)


def staged_specs(rules: ShardingRules, axes_tree, shapes_tree):
    """Specs from the rules, with the leading ``layers`` dim of stacked
    params additionally sharded over the ``stage`` axis."""
    def one(names, sds):
        spec = rules.spec_for(names, sds.shape)
        if names and names[0] == "layers":
            return P(*(("stage",) + tuple(spec)[1:]))
        return spec

    return jax.tree.map(one, axes_tree, shapes_tree, is_leaf=_is_axes)


def stage_only_specs(axes_tree):
    """shard_map in_specs: partial-manual mode may only name manual axes, so
    these specs carry *just* the stage dim; data/model sharding stays GSPMD-
    auto (applied at the jit level via :func:`staged_specs`)."""
    def one(names):
        if names and names[0] == "layers":
            return P("stage")
        return P()

    return jax.tree.map(one, axes_tree, is_leaf=_is_axes)


# ---------------------------------------------------------------------------
# uneven stages: layer allocation + padded stage-sharded layout
# ---------------------------------------------------------------------------


def even_stage_layers(n_rep: int, n_stages: int) -> tuple:
    """The classic even split; raises unless ``n_stages`` divides."""
    if n_rep % n_stages:
        raise ValueError(
            f"n_rep={n_rep} not divisible by {n_stages} stages; pass an "
            f"explicit stage_layers vector (e.g. from the hetero planner's "
            f"HeteroPlacement.layer_alloc) for uneven pipelines")
    return (n_rep // n_stages,) * n_stages


def check_stage_layers(stage_layers, n_rep: int, n_stages: int) -> tuple:
    sl = tuple(int(x) for x in stage_layers)
    if len(sl) != n_stages:
        raise ValueError(f"stage_layers {sl} has {len(sl)} entries for "
                         f"{n_stages} stages")
    if any(x < 1 for x in sl):
        raise ValueError(f"every stage needs >= 1 layer repeat, got {sl}")
    if sum(sl) != n_rep:
        raise ValueError(f"stage_layers {sl} sums to {sum(sl)}, "
                         f"expected n_rep={n_rep}")
    return sl


def stage_layers_from_alloc(stack: tfm.StackCfg, layer_alloc) -> tuple:
    """HeteroPlacement.layer_alloc (model *layers* per stage, the planner's
    unit) → per-stage pattern-*repeat* counts (the executor's unit).

    A stage's layer share must be a whole number of pattern repeats (a
    repeat is the scan/remat unit and cannot straddle a stage boundary);
    the planner's even/proportional splits satisfy this for single-block
    patterns (dense/moe-every-1/ssm) where repeats == layers."""
    plen = len(stack.pattern)
    bad = [a for a in layer_alloc if a % plen]
    if bad:
        raise ValueError(
            f"stage layer allocation {tuple(layer_alloc)} is not a multiple "
            f"of the {plen}-block scan pattern; re-plan with pp dividing "
            f"n_rep or a pattern-aligned allocation")
    out = tuple(a // plen for a in layer_alloc)
    if sum(out) != stack.n_rep:
        raise ValueError(f"layer_alloc {tuple(layer_alloc)} covers "
                         f"{sum(out)} repeats, model has {stack.n_rep}")
    return out


def pad_stage_stack(blocks, stage_layers):
    """(n_rep, …) stacked block params → padded ``(S·Lmax, …)`` layout.

    Stage ``s`` owns rows ``[s·Lmax, s·Lmax + stage_layers[s])``; pad rows
    are zero (the gated scan never reads their output, so they also
    receive exactly-zero gradients).  An even split is the identity."""
    sl = tuple(stage_layers)
    lmax = max(sl)
    if sl == (lmax,) * len(sl):
        return blocks                      # even: padded layout == stacked

    def one(p):
        out = jnp.zeros((len(sl) * lmax,) + p.shape[1:], p.dtype)
        off = 0
        for s, n in enumerate(sl):
            out = jax.lax.dynamic_update_slice_in_dim(
                out, p[off:off + n], s * lmax, axis=0)
            off += n
        return out

    return jax.tree.map(one, blocks)


def unpad_stage_stack(blocks, stage_layers):
    """Inverse of :func:`pad_stage_stack` (drops the pad rows) — for
    exporting a pipeline-trained checkpoint back to the canonical
    ``(n_rep, …)`` layout."""
    sl = tuple(stage_layers)
    lmax = max(sl)
    if sl == (lmax,) * len(sl):
        return blocks

    def one(p):
        return jnp.concatenate(
            [p[s * lmax:s * lmax + n] for s, n in enumerate(sl)], axis=0)

    return jax.tree.map(one, blocks)


def pipeline_params(model: Model, params: dict, stage_layers) -> dict:
    """Re-lay a standard param tree for the uneven pipeline executor."""
    out = dict(params)
    out["blocks"] = pad_stage_stack(params["blocks"], stage_layers)
    return out


def _padded_model_shapes(model: Model, stage_layers):
    shapes = model.param_shapes()
    return dict(shapes, blocks=jax.eval_shape(
        lambda b: pad_stage_stack(b, stage_layers), shapes["blocks"]))


def _apply_stack_gated(params, x, positions, stack: tfm.StackCfg, n_active):
    """:func:`repro.models.transformer.apply_stack` with the first
    ``n_active`` of ``stack.n_rep`` repeats live — repeat ``k >=
    n_active`` passes ``x`` through untouched and contributes no aux (and,
    via the ``where`` transpose, no gradient)."""

    def rep_body(x, inp):
        rep_params, k = inp
        aux = {"lb_loss": jnp.zeros((), jnp.float32),
               "z_loss": jnp.zeros((), jnp.float32)}
        y = x
        for i, bcfg in enumerate(stack.pattern):
            y, a, _ = tfm.apply_block(rep_params[f"p{i}"], y, positions,
                                      bcfg, stack)
            aux = jax.tree.map(jnp.add, aux, a)
        keep = k < n_active
        x = jnp.where(keep, y, x)
        aux = jax.tree.map(lambda a: jnp.where(keep, a, 0.0), aux)
        return x, aux

    body = tfm._remat_wrap(rep_body, stack.remat)
    ks = jnp.arange(stack.n_rep)
    if stack.scan and stack.n_rep > 1:
        x, auxs = jax.lax.scan(lambda c, p: body(c, p), x, (params, ks))
        aux = jax.tree.map(lambda a: a.sum(0), auxs)
    else:
        aux = {"lb_loss": jnp.zeros((), jnp.float32),
               "z_loss": jnp.zeros((), jnp.float32)}
        for r in range(stack.n_rep):
            rep_params = jax.tree.map(lambda p: p[r], params)
            x, a = body(x, (rep_params, ks[r]))
            aux = jax.tree.map(jnp.add, aux, a)
    return x, aux


def check_micro_divides(batch: int, micro_batches: int) -> int:
    """The ``B % M != 0`` guard: a truncated ``reshape(M, B // M, …)``
    would silently drop the trailing ``B % M`` sequences from the loss."""
    if micro_batches < 1:
        raise ValueError(f"micro_batches must be >= 1, got {micro_batches}")
    if batch % micro_batches:
        raise ValueError(
            f"global batch {batch} is not divisible by micro_batches="
            f"{micro_batches}; the truncated reshape would silently drop "
            f"{batch % micro_batches} sequence(s) from the loss — pick M "
            f"dividing B (or pad the batch)")
    return batch // micro_batches


# ---------------------------------------------------------------------------
# fused SPMD engine (shard_map + ppermute; autodiff = mirrored gpipe order)
# ---------------------------------------------------------------------------


def make_pipeline_loss(model: Model, mesh: Mesh, rules: ShardingRules, *,
                       micro_batches: int, stage_layers=None,
                       schedule: str = "gpipe"):
    """→ (loss_fn(params, tokens), param PartitionSpecs).

    ``params["blocks"]`` leaves
    live in the (possibly padded) stage-sharded layout of
    :func:`pipeline_params`; embed/head/norms are stage-replicated.
    ``stage_layers`` (default even) sets each stage's repeat count —
    uneven vectors come from the hetero planner's
    ``HeteroPlacement.layer_alloc``.  ``schedule`` is carried for
    planning (bubble/memory pricing, ``scan`` length is schedule-
    independent); on the fused engine autodiff always materializes the
    gpipe order — :func:`schedule_grads` is the order-faithful engine.

    Differentiable; composes with DP/TP because data/model axes stay
    GSPMD-auto inside the shard_map.
    """
    cfg = model.cfg
    stack = model.stack
    if stack is None:
        raise ValueError(
            "make_pipeline_loss pipelines decoder-LM stacks; encoder–"
            "decoder models pipeline over the two-tower cut instead — "
            "use make_encdec_pipeline_loss / make_encdec_pipeline_train_step")
    sched_mod.make_schedule(schedule, 2, 2)   # validate the name eagerly
    if schedule != "gpipe" and micro_batches > mesh.shape["stage"]:
        import warnings
        warnings.warn(
            f"schedule={schedule!r}: the fused SPMD engine materializes the "
            f"gpipe order under autodiff, so its real peak activation "
            f"memory is M={micro_batches} in-flight micro-batches, not the "
            f"schedule's min(M, S) — judge HBM feasibility at gpipe "
            f"pricing on this engine (schedule_grads is the order-faithful "
            f"executor)", stacklevel=2)
    S = mesh.shape["stage"]
    M = micro_batches
    if stage_layers is None:
        stage_layers = even_stage_layers(stack.n_rep, S)
    stage_layers = check_stage_layers(stage_layers, stack.n_rep, S)
    lmax = max(stage_layers)
    local_stack = dataclasses.replace(stack, n_rep=lmax)
    sl_arr = jnp.asarray(stage_layers, jnp.int32)
    norm = layers.make_norm(cfg.norm)[2]
    perm = [(i, i + 1) for i in range(S - 1)]

    def inner(params, tokens):
        sid = jax.lax.axis_index("stage")
        B, T = tokens.shape
        mb = check_micro_divides(B, M)
        toks_mb = tokens.reshape(M, mb, T)
        positions = jnp.broadcast_to(jnp.arange(T)[None], (mb, T))
        head_w = model._head_w(params).astype(cfg.adtype)
        n_active = sl_arr[sid]

        def tick(carry, t):
            recv, loss_acc, n_acc, aux_acc = carry
            # ---- stage 0 ingests micro-batch t; others take the wire ----
            tok_in = jax.lax.dynamic_index_in_dim(
                toks_mb, jnp.clip(t, 0, M - 1), axis=0, keepdims=False)
            x0 = layers.embed(params["embed"], tok_in).astype(cfg.adtype)
            x_in = jnp.where(sid == 0, x0, recv)
            # ---- my (gated, possibly padded) slice of the stack ----
            y, aux = _apply_stack_gated(params["blocks"], x_in, positions,
                                        local_stack, n_active)
            mb_here = t - sid                      # micro-batch at this stage
            w_here = ((mb_here >= 0) & (mb_here < M)).astype(jnp.float32)
            aux_acc = jax.tree.map(lambda a, d: a + w_here * d, aux_acc, aux)
            # ---- last stage computes the loss for micro-batch t-(S-1) ----
            out_mb = t - (S - 1)
            lab_tok = jax.lax.dynamic_index_in_dim(
                toks_mb, jnp.clip(out_mb, 0, M - 1), axis=0, keepdims=False)
            xf = norm(params["final_norm"], y)
            mask = jnp.ones((mb, T - 1), jnp.float32)
            nll, zl, n = chunked_xent(
                xf[:, :-1], head_w, lab_tok[:, 1:], mask, vocab=cfg.vocab,
                chunk=cfg.loss_chunk, z_loss_coef=cfg.z_loss_coef)
            w_out = (((out_mb >= 0) & (out_mb < M)) & (sid == S - 1)
                     ).astype(jnp.float32)
            loss_acc = loss_acc + w_out * (nll + zl)
            n_acc = n_acc + w_out * n
            # ---- ship activations down the pipe ----
            recv_next = jax.lax.ppermute(y, "stage", perm)
            return (recv_next, loss_acc, n_acc, aux_acc), None

        recv0 = jnp.zeros((mb, T, cfg.d_model), cfg.adtype)
        zero = jnp.zeros((), jnp.float32)
        aux0 = {"lb_loss": zero, "z_loss": zero}
        (_, loss_sum, n_sum, aux), _ = jax.lax.scan(
            tick, (recv0, zero, zero, aux0), jnp.arange(M + S - 1))
        # per-stage partial totals → global
        loss_sum = jax.lax.psum(loss_sum, "stage")
        n_sum = jax.lax.psum(n_sum, "stage")
        aux = jax.tree.map(lambda a: jax.lax.psum(a, "stage") / M, aux)
        return (loss_sum / jnp.maximum(n_sum, 1.0)
                + aux["lb_loss"] + aux["z_loss"])

    pspecs = staged_specs(rules, model.axes(),
                          _padded_model_shapes(model, stage_layers))
    sm_specs = stage_only_specs(model.axes())

    def loss_fn(params, tokens):
        with use_rules(rules):
            return jax.shard_map(
                inner, mesh=mesh, in_specs=(sm_specs, P()), out_specs=P(),
                axis_names=frozenset({"stage"}), check_vma=False,
            )(params, tokens)

    return loss_fn, pspecs


def make_pipeline_train_step(model: Model, mesh: Mesh, rules: ShardingRules,
                             optimizer, *, micro_batches: int,
                             stage_layers=None, schedule: str = "gpipe",
                             donate=True):
    """Jitted (params, opt_state, tokens, step) → (params, opt_state, loss).

    Accepts uneven
    ``stage_layers`` (params/optimizer state in the padded layout of
    :func:`pipeline_params`) and a schedule choice from the plan.
    """
    if stage_layers is None:
        stage_layers = even_stage_layers(model.stack.n_rep,
                                         mesh.shape["stage"])
    loss_fn, pspecs = make_pipeline_loss(
        model, mesh, rules, micro_batches=micro_batches,
        stage_layers=stage_layers, schedule=schedule)

    def step_fn(params, opt_state, tokens, step):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        params, opt_state = optimizer.apply(grads, opt_state, params, step)
        return params, opt_state, loss

    ns = lambda tree: jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                                   is_leaf=lambda t: isinstance(t, P))
    psh = ns(pspecs)
    pshapes = _padded_model_shapes(model, stage_layers)
    ospecs = staged_specs(rules, optimizer.state_axes(model.axes()),
                          jax.eval_shape(optimizer.init, pshapes))
    data_ax = tuple(a for a in ("pod", "data") if a in mesh.shape)
    tok_sh = NamedSharding(mesh, P(data_ax if len(data_ax) > 1 else
                                   (data_ax[0] if data_ax else None)))
    rep = NamedSharding(mesh, P())
    return jax.jit(step_fn,
                   in_shardings=(psh, ns(ospecs), tok_sh, rep),
                   out_shardings=(psh, ns(ospecs), rep),
                   donate_argnums=(0, 1) if donate else ())


# ---------------------------------------------------------------------------
# encoder–decoder two-tower pipeline (the M6/seamless multimodal cut)
# ---------------------------------------------------------------------------


def make_encdec_pipeline_loss(model: Model, mesh: Mesh, rules: ShardingRules,
                              *, micro_batches: int):
    """→ (loss_fn(params, frames, tokens), param PartitionSpecs).

    Encoder–decoder models have no interchangeable layer stack to split
    evenly — their natural pipeline cut is the segment edge between the
    towers (exactly the boundary the segment-aware planner refuses to
    move).  Stage 0 runs the (optional frontend adapter +) encoder on each
    micro-batch's frames and ships the ``(mb, S_src, d_model)`` memory
    down the wire; stage 1 embeds the target tokens, runs the decoder
    (self-attention + cross-attention over the received memory), and takes
    the loss.  M micro-batches drain in M + 1 ticks.

    Params are stage-*replicated* (each tower's weights are only touched
    on its own ``lax.cond`` branch; the shard_map transpose psums the
    per-stage cotangents, so gradients are exact).  Loss aggregation
    matches ``Model._loss_encdec``: ``Σ(nll+zl) / Σ n`` over micro-batches
    equals the full-batch value up to float reassociation.
    """
    cfg = model.cfg
    if cfg.family != "encdec" or model.ecfg is None:
        raise ValueError(
            f"make_encdec_pipeline_loss is the encoder–decoder engine; "
            f"family={cfg.family!r} pipelines via make_pipeline_loss")
    ecfg = model.ecfg
    S = mesh.shape["stage"]
    if S != 2:
        raise ValueError(
            f"the encdec pipeline is a strict 2-stage engine (encoder tower "
            f"| decoder tower), got a stage axis of size {S}")
    M = micro_batches
    norm = layers.make_norm(cfg.norm)[2]
    perm = [(0, 1)]

    def inner(params, frames, tokens):
        sid = jax.lax.axis_index("stage")
        B, S_src, _ = frames.shape
        T = tokens.shape[1]
        mb = check_micro_divides(B, M)
        frames_mb = frames.reshape(M, mb, S_src, cfg.d_model)
        toks_mb = tokens.reshape(M, mb, T)
        head_w = model._head_w(params).astype(cfg.adtype)

        def tick(carry, t):
            recv, loss_acc, n_acc = carry
            fr = jax.lax.dynamic_index_in_dim(
                frames_mb, jnp.clip(t, 0, M - 1), axis=0, keepdims=False)
            out_mb = t - 1
            tok = jax.lax.dynamic_index_in_dim(
                toks_mb, jnp.clip(out_mb, 0, M - 1), axis=0, keepdims=False)

            def enc_stage(op):
                fr, _recv, _tok = op
                x = fr.astype(cfg.adtype)
                if cfg.frontend is not None:
                    x = frontends.adapt(params["adapter"], x)
                mem = encdec_mod.encode(params["encdec"], x, ecfg)
                zero = jnp.zeros((), jnp.float32)
                return mem.astype(cfg.adtype), zero, zero

            def dec_stage(op):
                _fr, recv, tok = op
                dec_in = layers.embed(params["embed"],
                                      tok[:, :-1]).astype(cfg.adtype)
                x = encdec_mod.decode_train(params["encdec"], dec_in, recv,
                                            ecfg)
                xf = norm(params["final_norm"], x)
                mask = jnp.ones((mb, T - 1), jnp.float32)
                nll, zl, n = chunked_xent(
                    xf, head_w, tok[:, 1:], mask, vocab=cfg.vocab,
                    chunk=cfg.loss_chunk, z_loss_coef=cfg.z_loss_coef)
                return recv, nll + zl, n

            y, l_mb, n_mb = jax.lax.cond(sid == 0, enc_stage, dec_stage,
                                         (fr, recv, tok))
            w_out = (((out_mb >= 0) & (out_mb < M)) & (sid == S - 1)
                     ).astype(jnp.float32)
            loss_acc = loss_acc + w_out * l_mb
            n_acc = n_acc + w_out * n_mb
            recv_next = jax.lax.ppermute(y, "stage", perm)
            return (recv_next, loss_acc, n_acc), None

        recv0 = jnp.zeros((mb, S_src, cfg.d_model), cfg.adtype)
        zero = jnp.zeros((), jnp.float32)
        (_, loss_sum, n_sum), _ = jax.lax.scan(
            tick, (recv0, zero, zero), jnp.arange(M + 1))
        loss_sum = jax.lax.psum(loss_sum, "stage")
        n_sum = jax.lax.psum(n_sum, "stage")
        return loss_sum / jnp.maximum(n_sum, 1.0)

    pspecs = rules.param_specs_tree(model.axes(), model.param_shapes(),
                                    fsdp=False)
    sm_specs = jax.tree.map(lambda names: P(), model.axes(), is_leaf=_is_axes)

    def loss_fn(params, frames, tokens):
        with use_rules(rules):
            return jax.shard_map(
                inner, mesh=mesh, in_specs=(sm_specs, P(), P()),
                out_specs=P(), axis_names=frozenset({"stage"}),
                check_vma=False,
            )(params, frames, tokens)

    return loss_fn, pspecs


def make_encdec_pipeline_train_step(model: Model, mesh: Mesh,
                                    rules: ShardingRules, optimizer, *,
                                    micro_batches: int, donate=True):
    """Jitted (params, opt_state, frames, tokens, step) → (params,
    opt_state, loss) through the two-tower encdec pipeline."""
    loss_fn, pspecs = make_encdec_pipeline_loss(
        model, mesh, rules, micro_batches=micro_batches)

    def step_fn(params, opt_state, frames, tokens, step):
        loss, grads = jax.value_and_grad(loss_fn)(params, frames, tokens)
        params, opt_state = optimizer.apply(grads, opt_state, params, step)
        return params, opt_state, loss

    ns = lambda tree: jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                                   is_leaf=lambda t: isinstance(t, P))
    psh = ns(pspecs)
    ospecs = rules.param_specs_tree(
        optimizer.state_axes(model.axes()),
        jax.eval_shape(optimizer.init, model.param_shapes()), fsdp=False)
    data_ax = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dspec = P(data_ax if len(data_ax) > 1 else
              (data_ax[0] if data_ax else None))
    batch_sh = NamedSharding(mesh, dspec)
    rep = NamedSharding(mesh, P())
    return jax.jit(step_fn,
                   in_shardings=(psh, ns(ospecs), batch_sh, batch_sh, rep),
                   out_shardings=(psh, ns(ospecs), rep),
                   donate_argnums=(0, 1) if donate else ())


# ---------------------------------------------------------------------------
# schedule interpreter (order-faithful reference engine, single device)
# ---------------------------------------------------------------------------


def _stage_slices(blocks, stage_layers):
    """Standard (n_rep, …) stacked params → per-stage python-sliced trees."""
    out, off = [], 0
    for n in stage_layers:
        out.append(jax.tree.map(lambda p, a=off, b=off + n: p[a:b], blocks))
        off += n
    return out


def schedule_grads(model: Model, params: dict, tokens, *,
                   micro_batches: int, schedule="1f1b", stage_layers=None,
                   n_stages: int | None = None):
    """Execute one train step's fwd+bwd work in *exactly* the order of a
    :class:`~repro.core.schedule.Schedule` tick table.

    The reference engine behind the schedule-equivalence tests: stages are
    python-level slices of the standard ``(n_rep, …)`` param tree (uneven
    ``stage_layers`` welcome, no padding needed at this level); each fwd
    slot runs the stage and saves only the stage *input* activation; each
    bwd slot recomputes the stage under ``jax.vjp`` (stage-granular remat)
    and routes the cotangent up the pipe.  Because the math per
    (stage, micro-batch) is fixed, every valid schedule yields the same
    loss and gradients — only the activation-buffer profile differs, and
    it is audited: the returned ``stats["peak_in_flight"]`` /
    ``stats["per_stage_in_flight"]`` are measured from the live buffer
    and must equal the schedule's own accounting.

    Returns ``(loss, grads, stats)`` with ``grads`` in the standard param
    layout.  Wrap in ``jax.jit`` for speed; the table is unrolled.
    """
    cfg = model.cfg
    stack = model.stack
    if stack is None:
        raise ValueError(
            "schedule_grads interprets decoder-LM stacks; encoder–decoder "
            "models use the two-tower make_encdec_pipeline_* engine")
    M = micro_batches
    if isinstance(schedule, sched_mod.Schedule):
        sc = schedule
        if sc.n_micro != M:
            raise ValueError(f"schedule has n_micro={sc.n_micro}, "
                             f"micro_batches={M}")
    else:
        if n_stages is None:
            n_stages = len(stage_layers) if stage_layers is not None else 1
        sc = sched_mod.make_schedule(schedule, n_stages, M)
    S = sc.n_stages
    if stage_layers is None:
        stage_layers = even_stage_layers(stack.n_rep, S)
    stage_layers = check_stage_layers(stage_layers, stack.n_rep, S)

    B, T = tokens.shape
    mb_size = check_micro_divides(B, M)
    toks_mb = tokens.reshape(M, mb_size, T)
    positions = jnp.broadcast_to(jnp.arange(T)[None], (mb_size, T))
    n_total = float(M * mb_size * (T - 1))     # all-ones loss mask
    norm = layers.make_norm(cfg.norm)[2]
    tied = cfg.tie_embeddings
    shared_keys = ["embed", "final_norm"] + ([] if tied else ["head"])
    shared = {k: params[k] for k in shared_keys}
    stage_blocks = _stage_slices(params["blocks"], stage_layers)
    stage_stacks = [dataclasses.replace(stack, n_rep=n)
                    for n in stage_layers]

    def stage_call(s, blocks_s, sh, x, tok):
        """One stage's work on one micro-batch → (y, scalar loss contrib)."""
        if s == 0:
            x = layers.embed(sh["embed"], tok).astype(cfg.adtype)
        y, aux = tfm.apply_stack(blocks_s, x, positions, stage_stacks[s])
        contrib = (aux["lb_loss"] + aux["z_loss"]) / M
        if s == S - 1:
            xf = norm(sh["final_norm"], y)
            head_w = (sh["embed"]["table"].T if tied
                      else sh["head"]["w"]).astype(cfg.adtype)
            mask = jnp.ones((mb_size, T - 1), jnp.float32)
            nll, zl, _ = chunked_xent(
                xf[:, :-1], head_w, tok[:, 1:], mask, vocab=cfg.vocab,
                chunk=cfg.loss_chunk, z_loss_coef=cfg.z_loss_coef)
            contrib = contrib + (nll + zl) / n_total
        return y, contrib

    # one jitted fwd and one jitted bwd per stage — micro-batches reuse the
    # compiled program, so trace cost is O(S), not O(ticks)
    def make_fwd(s):
        return jax.jit(lambda b, sh, x, tok: stage_call(s, b, sh, x, tok))

    def make_bwd(s):
        def bwd(b, sh, x, tok, dy):
            (_, _), vjp = jax.vjp(
                lambda bb, ss, xx: stage_call(s, bb, ss, xx, tok), b, sh, x)
            return vjp((dy, jnp.ones((), jnp.float32)))
        return jax.jit(bwd)

    fwd_jit = [make_fwd(s) for s in range(S)]
    bwd_jit = [make_bwd(s) for s in range(S)]
    x_dummy = jnp.zeros((mb_size, T, cfg.d_model), cfg.adtype)

    zerot = lambda tree: jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), tree)
    g_blocks = [zerot(b) for b in stage_blocks]
    g_shared = zerot(shared)
    loss = jnp.zeros((), jnp.float32)
    saved = {}                       # (s, mb) -> stage input activation
    cot = {}                         # (s, mb) -> cotangent of stage output
    peaks = [0] * S
    live = [0] * S
    for t, s, mb, phase in sc.slots():
        if phase == sched_mod.FWD:
            x_in = x_dummy if s == 0 else saved.pop(("wire", s, mb))
            y, c = fwd_jit[s](stage_blocks[s], shared, x_in, toks_mb[mb])
            loss = loss + c
            saved[(s, mb)] = x_in     # stage-granular remat: keep input only
            live[s] += 1
            peaks[s] = max(peaks[s], live[s])
            if s < S - 1:
                saved[("wire", s + 1, mb)] = y
        else:
            x_in = saved.pop((s, mb))
            live[s] -= 1
            dy = cot.pop((s, mb), jnp.zeros((mb_size, T, cfg.d_model),
                                            cfg.adtype))
            db, dsh, dx = bwd_jit[s](stage_blocks[s], shared, x_in,
                                     toks_mb[mb], dy)
            g_blocks[s] = jax.tree.map(
                lambda a, d: a + d.astype(jnp.float32), g_blocks[s], db)
            g_shared = jax.tree.map(
                lambda a, d: a + d.astype(jnp.float32), g_shared, dsh)
            if s > 0:
                cot[(s - 1, mb)] = dx
    assert not saved and not cot, "schedule left dangling buffers"
    if peaks != sc.per_stage_in_flight():
        raise AssertionError(
            f"buffer audit: measured in-flight peaks {peaks} != schedule's "
            f"accounting {sc.per_stage_in_flight()}")

    grads = dict(g_shared)
    grads["blocks"] = jax.tree.map(
        lambda *xs: jnp.concatenate(xs, axis=0), *g_blocks)
    stats = {"n_ticks": sc.n_ticks,
             "bubble_fraction": sc.bubble_fraction(),
             "peak_in_flight": max(peaks),
             "per_stage_in_flight": peaks,
             "stage_layers": stage_layers}
    return loss, grads, stats
