"""Whale core: strategy primitives, IR, engine, cost model, auto-parallel.

The user-facing surface mirrors the paper's API (``import repro as wh``):

    with wh.cluster(mesh_shape=(2, 4), axis_names=("data", "model")):
        with wh.replica():
            h = wh.sub("backbone", net)(params, x)
        with wh.split(dim=-1):
            logits = wh.sub("fc", head)(head_params, h)
"""
from repro.core.auto import (auto_parallel, graph_from_taskgraph,  # noqa: F401
                             search)
from repro.core.cost_model import (ClusterSpec, DeviceGroup, Hardware,  # noqa: F401
                                   ModelGraph, P100_16G, SegmentMeta,
                                   StrategySpec, T4_16G, TPU_V5E,
                                   V100_PAPER, WorkloadMeta,
                                   step_cost, throughput)
from repro.core.graph_opt import (GradAgg, LoweredGraph,  # noqa: F401
                                  StrategyNestingError, bridge_cost,
                                  compile_nested_plan, insert_bridges,
                                  lower, place_grad_aggregation, plan_bridge,
                                  validate_nesting)
from repro.core.hetero import (HeteroPlacement, balance_batch,  # noqa: F401
                               balance_stages, hetero_step_cost,
                               plan_placement)
from repro.core.ir import (Bridge, Edge, Subgraph, TaskGraph,  # noqa: F401
                           TensorMeta, capture_meta)
from repro.core.planner import (ExecutionPlan, compile_plan,  # noqa: F401
                                compile_plan_from_cluster, mesh_for_strategy,
                                rules_for_strategy, strategy_from_taskgraph)
from repro.core.sharding import (ShardingRules, constrain, hybrid_rules,  # noqa: F401
                                 make_mesh, use_rules)
from repro.core.strategies import (cluster, pipeline, replica, split,  # noqa: F401
                                   stage, sub)
from repro.core.strategies import auto_parallel as auto_scope  # noqa: F401
from repro.core.vdevice import Cluster, VirtualDevice  # noqa: F401
