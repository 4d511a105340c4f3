"""Discrete-event cluster simulator for the WOW reproduction.

The three scheduling policies it compares (Nextflow original, the Common
Workflow Scheduler baseline and WOW) are the runtime adapters of
``repro.core.adapter``; the ``*Strategy`` names are those classes.
"""
from ..core.adapter import CwsAdapter as CwsStrategy
from ..core.adapter import OrigAdapter as OrigStrategy
from ..core.adapter import RuntimeAdapter as BaseStrategy
from ..core.adapter import WowAdapter as WowStrategy
from ..core.adapter import make_adapter as make_strategy
from .dfs import CephModel, DfsModel, NfsModel
from .engine import (DeadlockError, SimConfig, Simulation, run_traffic,
                     run_workflow)
from .metrics import (SimResult, TrafficResult, compute_traffic_result,
                      efficiency, gini, jain, percentile)
from .network import Flow, FlowManager, ReferenceFlowManager, build_links
from .topology import Topology, TopologySpec
from .traffic import (ArrivalSpec, InstanceRecord, RetryPolicy, TenantSpec,
                      TrafficConfig, arrival_schedule)
from .workflow import Workflow

__all__ = [
    "ArrivalSpec", "BaseStrategy", "CephModel", "CwsStrategy",
    "DeadlockError", "DfsModel", "Flow", "FlowManager", "InstanceRecord",
    "NfsModel", "OrigStrategy", "ReferenceFlowManager", "RetryPolicy",
    "SimConfig", "SimResult", "Simulation", "TenantSpec", "Topology",
    "TopologySpec", "TrafficConfig", "TrafficResult", "Workflow",
    "WowStrategy", "arrival_schedule", "build_links",
    "compute_traffic_result", "efficiency", "gini", "jain",
    "make_strategy", "percentile", "run_traffic", "run_workflow",
]
