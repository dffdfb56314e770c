"""Multi-Dimension → mesh mapping (Whale's unified dimension abstraction).

Tensors in the model substrate are annotated with *logical* dimension names
("batch", "seq", "q_heads", "mlp", "experts", "vocab", ...).  A
:class:`ShardingRules` object — produced by the planner from the user's
strategy scopes — maps each logical name to zero or more physical mesh axes.
Models call :func:`constrain` / :func:`spec_for`; they never mention mesh
axes, which is what lets one model definition run under any Whale strategy
(replica / split / stage / pipeline / hybrid).

Divisibility pruning: when a logical dim's size does not divide evenly over
its assigned mesh axes, the assignment is dropped for that tensor (e.g. a
kv_heads=8 tensor on a 16-way model axis stays replicated).  This mirrors
Whale's planner choosing a legal sharding per subgraph rather than failing.
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices=None) -> Mesh:
    """The one mesh constructor: every axis ``AxisType.Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which
    ``with_sharding_constraint`` refuses the NamedSharding specs
    :func:`constrain` emits and partially-manual ``shard_map`` bodies see
    ``(Manual, Explicit, …)`` meshes.  The planner's rules are GSPMD
    annotations, so every mesh here is built Auto.
    """
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


# logical name -> mesh axis name, tuple of axis names, or None (replicated)
RuleMap = Mapping[str, object]

_tls = threading.local()


@dataclass
class ShardingRules:
    mesh: Mesh
    rules: dict = field(default_factory=dict)

    def axis_size(self, axes) -> int:
        if axes is None:
            return 1
        if isinstance(axes, str):
            axes = (axes,)
        n = 1
        for a in axes:
            n *= self.mesh.shape.get(a, 1)
        return n

    def spec_for(self, names: Sequence[str | None], shape: Sequence[int] | None = None,
                 ) -> P:
        """Build a PartitionSpec for logical dim names, pruning non-divisible axes.

        Mesh axes may be used at most once in a spec; first-come wins (matching
        GSPMD's constraint that an axis shards a single dim).
        """
        used: set[str] = set()
        parts = []
        for i, name in enumerate(names):
            assigned = self.rules.get(name) if name is not None else None
            if assigned is None:
                parts.append(None)
                continue
            axes = (assigned,) if isinstance(assigned, str) else tuple(assigned)
            axes = tuple(a for a in axes if a in self.mesh.shape and a not in used)
            if not axes:
                parts.append(None)
                continue
            if shape is not None:
                # prune trailing axes until divisible
                while axes:
                    n = 1
                    for a in axes:
                        n *= self.mesh.shape[a]
                    if shape[i] % n == 0:
                        break
                    axes = axes[:-1]
                if not axes:
                    parts.append(None)
                    continue
            used.update(axes)
            parts.append(axes[0] if len(axes) == 1 else tuple(axes))
        return P(*parts)

    def sharding_for(self, names, shape=None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec_for(names, shape))

    def param_spec(self, names: Sequence[str | None], shape: Sequence[int],
                   *, fsdp_axes: Sequence[str] = (), min_fsdp_size: int = 65536,
                   ) -> P:
        """TP spec from the rules + ZeRO-3/FSDP extension: the largest
        still-unsharded, divisible, non-scan dim takes the data axes."""
        spec = self.spec_for(names, shape)
        fa = tuple(a for a in fsdp_axes if a in self.mesh.shape)
        if not fa or int(np.prod(shape)) < min_fsdp_size:
            return spec
        used = set()
        for p in spec:
            for a in ((p,) if isinstance(p, str) else (p or ())):
                used.add(a)
        fa = tuple(a for a in fa if a not in used)
        if not fa:
            return spec
        n = 1
        for a in fa:
            n *= self.mesh.shape[a]
        parts = list(spec)
        cands = [i for i in range(len(shape))
                 if parts[i] is None and (names[i] != "layers")
                 and shape[i] % n == 0]
        if not cands:
            return spec
        i = max(cands, key=lambda j: shape[j])
        parts[i] = fa[0] if len(fa) == 1 else fa
        return P(*parts)

    def param_specs_tree(self, axes_tree, shapes_tree, *, fsdp: bool = True,
                         fsdp_axes: Sequence[str] = ("pod", "data")):
        fa = fsdp_axes if fsdp else ()
        return jax.tree.map(
            lambda names, sds: self.param_spec(names, sds.shape, fsdp_axes=fa),
            axes_tree, shapes_tree,
            is_leaf=lambda t: isinstance(t, tuple) and all(
                isinstance(e, (str, type(None))) for e in t),
        )


def manual_axis_names() -> frozenset:
    """Mesh axes currently bound manual (inside shard_map); else empty."""
    am = jax.sharding.get_abstract_mesh()
    if am is None or am.empty:
        return frozenset()
    return frozenset(a for a, t in zip(am.axis_names, am.axis_types)
                     if t == jax.sharding.AxisType.Manual)


def current_rules() -> ShardingRules | None:
    return getattr(_tls, "rules", None)


@contextlib.contextmanager
def use_rules(rules: ShardingRules | None):
    prev = getattr(_tls, "rules", None)
    _tls.rules = rules
    try:
        yield rules
    finally:
        _tls.rules = prev


def constrain(x: jax.Array, names: Sequence[str | None]) -> jax.Array:
    """Apply a logical sharding constraint if rules are active; else identity.

    Inside a partially-manual ``shard_map`` (the pipeline path) the context
    mesh differs from the rules' concrete mesh in axis *types*, so the spec
    is passed bare (resolved against the context mesh) with any manual axes
    stripped — those dims are already physically local.
    """
    rules = current_rules()
    if rules is None:
        return x
    spec = rules.spec_for(names, x.shape)
    manual = manual_axis_names()
    if manual:
        parts = tuple(None if (p in manual or (isinstance(p, tuple) and
                                               set(p) & manual)) else p
                      for p in spec)
        return jax.lax.with_sharding_constraint(x, P(*parts))
    return jax.lax.with_sharding_constraint(x, NamedSharding(rules.mesh, spec))


def tree_specs(axes_tree, shapes_tree, rules: ShardingRules):
    """Map an axes pytree (+ matching ShapeDtypeStruct pytree) to PartitionSpecs."""
    return jax.tree.map(
        lambda names, sds: rules.spec_for(names, sds.shape),
        axes_tree, shapes_tree,
        is_leaf=lambda t: isinstance(t, tuple) and all(isinstance(e, (str, type(None))) for e in t),
    )


def tree_shardings(axes_tree, shapes_tree, rules: ShardingRules):
    specs = tree_specs(axes_tree, shapes_tree, rules)
    return jax.tree.map(lambda s: NamedSharding(rules.mesh, s), specs,
                        is_leaf=lambda t: isinstance(t, P))


# ---------------------------------------------------------------------------
# canonical rule sets (the planner composes/overrides these)
# ---------------------------------------------------------------------------

def hybrid_rules(mesh: Mesh, *, fsdp: bool = True, data_axes=("pod", "data"),
                 model_axis: str = "model",
                 context_parallel: bool = False,
                 expert_axis: str | None = None) -> ShardingRules:
    """Whale Case-2 style hybrid: replica over data axes × operator split over model.

    - batch           → all data axes (pod-major)
    - TP targets      → model axis (q_heads/kv_heads/mlp/experts/vocab/ssm_heads)
    - FSDP (ZeRO-3)   → params additionally sharded over data axes on 'embed'
    - seq_shard       → decode-time KV sequence dim (flash-decode combine)
    - context_parallel → the *query sequence* dim additionally takes the
      model axis.  This is Whale's `split` applied along the sequence
      Multi-Dimension: for archs whose head count does not divide the model
      axis (gemma: 8 heads, qwen2-vl: 12 heads on 16 shards) head-sharding
      prunes and attention would otherwise replicate 16× — sharding q-seq
      restores the 1/16 work split (KV stays replicated, MQA-style CP).
    - expert_axis → a dedicated *expert-parallel* mesh axis (the nested
      ``replica{split[experts]}`` hybrid of graph_opt): the `experts`
      Multi-Dimension shards over it first, ahead of the model axis, so a
      mesh carrying an ``expert`` axis places whole experts per shard and
      the graph optimizer's all-to-all bridges carry the dispatch.  The
      explicit shard_map execution path is ``models.moe.moe_block_ep``.
    """
    data_axes = tuple(a for a in data_axes if a in mesh.shape)
    if expert_axis is None and "expert" in mesh.shape:
        expert_axis = "expert"
    rules = {
        "batch": data_axes if len(data_axes) > 1 else (data_axes[0] if data_axes else None),
        # NOTE: a full-sequence-parallel variant ("seq" → model axis, the
        # residual stream staying seq-sharded through the block) was tried
        # and REFUTED in §Perf iteration 3: GSPMD falls into "involuntary
        # full rematerialization" on the (seq × d_ff) 2-D-conflicting MLP
        # grads and re-shards whole weight matrices per layer.  Only the
        # attention q/out path is seq-sharded (q_seq below).
        "seq": None,
        "embed": None,
        "q_heads": model_axis,
        "kv_heads": model_axis,
        "head_dim": None,
        "mlp": model_axis,
        "experts": ((expert_axis, model_axis)
                    if expert_axis and expert_axis in mesh.shape
                    else model_axis),
        # fallback: when `experts` prunes (E ∤ model axis, e.g. grok-1's 8
        # experts on 16 shards) the within-expert d_ff takes the model axis
        # instead (expert tensor parallelism).  spec_for's first-come-wins
        # rule arbitrates — see models/moe.py docstring.
        "expert_mlp": model_axis,
        "vocab": model_axis,
        "ssm_heads": model_axis,
        "state": None,
        "conv": None,
        "layers": None,
        # sequence dim of q when head-sharding is impossible (see above)
        "q_seq": (model_axis,) if context_parallel else None,
        "kv_seq": (model_axis,),            # decode KV cache sequence shards
        "fsdp": data_axes if fsdp else None,  # weight dim tagged for ZeRO-3
    }
    return ShardingRules(mesh=mesh, rules=rules)
