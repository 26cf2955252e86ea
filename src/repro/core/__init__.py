"""The paper's primary contribution: partial-sum-aware feature-map
partitioning (first-order analytical bandwidth model + optimal partition) and
the active memory controller, plus the TPU-native generalization to matmul
block tiling.

The planning implementation lives in ``repro.plan`` (one Workload ->
Schedule -> Execution pipeline); this package keeps the paper-domain pieces:

  cnn_zoo.py      the paper's eight CNNs as programmatic layer tables
  amc.py          executable, instrumented active-memory-controller model
                  (executes + validates ``repro.plan`` Schedules)
  planner.py      whole-network partition schedules (wraps ``plan.plan_many``)
"""

from repro.core.cnn_zoo import (PAPER_CNNS, PAPER_TABLE3, ConvLayer, get_cnn,
                                get_cnn_graph_spec)
from repro.core.planner import NetworkPlan, plan_network

__all__ = [
    "PAPER_CNNS", "PAPER_TABLE3", "ConvLayer", "get_cnn", "get_cnn_graph_spec",
    "NetworkPlan", "plan_network",
]
