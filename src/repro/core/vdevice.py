"""Virtual devices (Whale abstraction #3).

A :class:`VirtualDevice` is a named group of physical devices; a
:class:`Cluster` owns the physical `jax.sharding.Mesh` and hands out virtual
devices.  Strategy scopes attach subgraphs to virtual devices; the planner
maps a virtual device onto mesh axes (replica groups ride the `data` axes,
operator shards the `model` axis, pipeline stages a `stage` axis) — see
DESIGN.md §4.

On TPU the mesh-axis order *is* the topology mapping: minor axes are
ICI-contiguous, the outermost (`pod`) axis crosses DCN — choosing which
logical axis lands where is exactly Whale's "choose the proper VD for a
Subgraph according to cluster topology".

Heterogeneous clusters (DESIGN.md §2): a Cluster may carry a
:class:`~repro.core.cost_model.ClusterSpec` describing per-device-group
hardware tables; virtual devices are then tagged with the hardware they
land on, and the planner/auto layers use the spec to balance work.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import Mesh

from repro.core.sharding import make_mesh


@dataclasses.dataclass(frozen=True)
class VirtualDevice:
    """A logical device group = a sub-rectangle of the mesh."""
    name: str
    axes: tuple            # mesh axes this VD spans
    index: int = 0         # which slice along the partitioning axis (stages)
    hardware: str | None = None   # Hardware.name this VD lands on (hetero)

    def size(self, mesh: Mesh) -> int:
        return int(np.prod([mesh.shape[a] for a in self.axes]))


class Cluster:
    """Physical cluster + virtual-device factory (Whale `wh.cluster`).

    Also the ambient context that strategy scopes and `wh.sub` record into.
    """

    _active: list = []

    def __init__(self, mesh: Mesh | None = None, *, mesh_shape: tuple | None = None,
                 axis_names: tuple | None = None, layout: dict | None = None,
                 spec=None):
        if mesh is None:
            if mesh_shape is None:
                n = len(jax.devices())
                mesh_shape, axis_names = (n,), ("data",)
            axis_names = axis_names or tuple(
                f"ax{i}" for i in range(len(mesh_shape)))
            mesh = make_mesh(mesh_shape, axis_names)
        self.mesh = mesh
        self.layout = layout or {}
        # per-device-group Hardware tables (cost_model.ClusterSpec) — None
        # means "treat as homogeneous" (every pre-existing call site)
        self.spec = spec
        self.taskgraph = None   # filled by strategies.trace / scopes
        self._scope_stack: list = []

    # --- context management (the `with wh.cluster():` API) ---
    def __enter__(self):
        Cluster._active.append(self)
        from repro.core.ir import TaskGraph
        if self.taskgraph is None:
            self.taskgraph = TaskGraph()
        return self

    def __exit__(self, *exc):
        Cluster._active.pop()
        return False

    @classmethod
    def current(cls) -> "Cluster | None":
        return cls._active[-1] if cls._active else None

    # --- heterogeneous hardware tags ---
    def _uniform_hw(self) -> str | None:
        if self.spec is not None and self.spec.is_homogeneous:
            return self.spec.groups[0].hw.name
        return None

    def hardware_for_stage(self, index: int, n_stages: int) -> str | None:
        """Hardware tag for pipeline stage ``index`` of ``n_stages``.

        Delegates to :func:`repro.core.hetero.stage_groups_for` — the
        same dealing the planner prices — so tags always agree with a
        realizable placement.  A layout the planner would reject (groups
        don't tile whole stages) gets no tag rather than a wrong one.
        """
        if self.spec is None:
            return None
        from repro.core.cost_model import StrategySpec
        from repro.core.hetero import stage_groups_for
        per_stage, rem = divmod(self.spec.n_devices, n_stages)
        if rem or per_stage == 0:
            return None
        try:
            sgroups = stage_groups_for(
                self.spec, StrategySpec(dp=per_stage, pp=n_stages))
        except ValueError:
            return None
        return sgroups[index].hw.name

    # --- virtual devices ---
    def replica_vd(self) -> VirtualDevice:
        axes = tuple(a for a in ("pod", "data") if a in self.mesh.shape)
        return VirtualDevice("replica", axes, hardware=self._uniform_hw())

    def split_vd(self) -> VirtualDevice:
        ax = "model" if "model" in self.mesh.shape else self.mesh.axis_names[-1]
        return VirtualDevice("split", (ax,), hardware=self._uniform_hw())

    def hybrid_vd(self) -> VirtualDevice:
        """Nested replica{split}: one VD spanning the data AND model axes
        (the subgraph is replicated over data, sharded over model)."""
        axes = tuple(a for a in ("pod", "data") if a in self.mesh.shape)
        ax = "model" if "model" in self.mesh.shape else self.mesh.axis_names[-1]
        return VirtualDevice("hybrid", axes + (ax,),
                             hardware=self._uniform_hw())

    def stage_vd(self, index: int, n_stages: int | None = None) -> VirtualDevice:
        ax = "stage" if "stage" in self.mesh.shape else self.mesh.axis_names[0]
        if n_stages is None:
            # the stage axis size IS the pipeline depth on a staged mesh —
            # existing call sites (wh.sub tracing) get tags for free
            n_stages = self.mesh.shape.get("stage")
        hw = self._uniform_hw()
        if hw is None and self.spec is not None and n_stages:
            hw = self.hardware_for_stage(index, n_stages)
        return VirtualDevice(f"stage{index}", (ax,), index, hardware=hw)

    @property
    def n_devices(self) -> int:
        return self.mesh.devices.size
