"""Unified Workload -> Schedule -> Execution planning API.

This package is the single front door for all partition/traffic planning in
the repo — the paper's conv channel partitions (eqs 1-7 + the active memory
controller) and their TPU generalization to VMEM GEMM blocks share one
pipeline:

    from repro import plan

    wl = plan.ConvWorkload.from_layer(get_cnn("resnet18")[5])
    p = plan.plan(wl, budget=2048, strategy="paper_opt", controller="active")
    p.schedule            # Schedule(kind="conv", bm=m, bn=n, ...)
    p.traffic             # TrafficReport(interconnect_words=..., bytes=...)

    gemm = plan.MatmulWorkload(m=8192, n=28672, k=8192)
    plan.plan(gemm, strategy="exhaustive_vmem", controller="active")

Consumers: the Pallas kernels accept ``schedule=`` directly, the AMC
simulator executes + cross-validates a `Schedule` against the analytical
`TrafficReport`, and ``core.planner.plan_network`` is a thin wrapper over
``plan_many``. The traffic model has one evaluator per workload kind,
`conv_model.conv_terms` and `gemm_model.matmul_terms`; every report,
objective and check reads it.
"""

from repro.plan import dse, fleet, graph, netplan, objectives, space
from repro.plan.graph import NetworkGraph, Node, Tensor
from repro.plan.netplan import (DEFAULT_RESIDENCY_BYTES, EdgePlan, NetPlan,
                                NodePlan, PlanContext,
                                clear_plan_graph_cache, network_report,
                                plan_graph, plan_graph_cache_info)
from repro.plan.fleet import plan_graphs
from repro.plan.api import (DEFAULT_P_MACS, Plan, clear_plan_cache,
                            coerce_strategy, default_budget,
                            min_network_traffic, network_traffic, plan,
                            plan_cache_info, plan_many)
from repro.plan.conv_model import optimal_m_realvalued
from repro.plan.dse import (Constraint, SearchResult, StrategySpec,
                            certify_space, register_strategy,
                            unregister_strategy)
from repro.plan.gemm_model import (DEFAULT_VMEM_BUDGET, LANE, SUBLANE,
                                   VMEM_BYTES, MatmulBlocks)
from repro.plan.objectives import (OBJECTIVES, Objective, get_objective,
                                   register_objective)
from repro.plan.planners import (PLANNERS, Planner, get_planner,
                                 register_planner)
from repro.plan.schedule import Controller, Partition, Schedule, Strategy
from repro.plan.space import Candidates, SearchSpace
from repro.plan.traffic import TrafficReport, traffic_report
from repro.plan.workload import (ConvWorkload, MatmulWorkload, Workload,
                                 conv_workloads, transformer_matmuls)

__all__ = [
    "Plan", "plan", "plan_many", "plan_cache_info", "clear_plan_cache",
    "default_budget", "network_traffic", "min_network_traffic",
    "coerce_strategy",
    "DEFAULT_P_MACS", "DEFAULT_VMEM_BUDGET", "VMEM_BYTES", "LANE", "SUBLANE",
    "Planner", "PLANNERS", "register_planner", "get_planner",
    "Controller", "Partition", "Schedule", "Strategy",
    "TrafficReport", "traffic_report", "MatmulBlocks",
    "ConvWorkload", "MatmulWorkload", "Workload", "conv_workloads",
    "transformer_matmuls", "optimal_m_realvalued",
    # --- design-space exploration (repro.plan.dse) ---
    "dse", "objectives", "space",
    "Constraint", "SearchResult", "StrategySpec", "certify_space",
    "register_strategy", "unregister_strategy",
    "OBJECTIVES", "Objective", "get_objective", "register_objective",
    "Candidates", "SearchSpace",
    # --- network-graph planning (repro.plan.graph / repro.plan.netplan) ---
    "graph", "netplan", "NetworkGraph", "Node", "Tensor",
    "NetPlan", "NodePlan", "EdgePlan", "plan_graph", "network_report",
    "DEFAULT_RESIDENCY_BYTES",
    # --- fleet planning (repro.plan.fleet) ---
    "fleet", "plan_graphs", "PlanContext",
    "plan_graph_cache_info", "clear_plan_graph_cache",
]
