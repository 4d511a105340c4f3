"""WOW core: the paper's contribution (3-step scheduler + DPS + priorities).

Environment-free -- the discrete-event simulator (`repro.sim`) and the JAX
runtime adapter (`repro.runtime`) both drive these classes.
"""
from .adapter import (ADAPTER_API, CwsAdapter, OrigAdapter, RuntimeAdapter,
                      WowAdapter, assert_implements, make_adapter)
from .dps import DataPlacementService
from .ilp import (AssignmentProblem, FingerprintCache,
                  IncrementalAssignmentSolver, component_fingerprint,
                  decompose, solve, solve_exact, solve_greedy,
                  solve_monolithic)
from .nodearray import NodeCapacityArray
from .priority import abstract_ranks, assign_priorities, priority_value
from .readyset import NodeOrder, ReadySet, ShapeIndex
from .reference import ReferenceWowScheduler
from .scheduler import WowScheduler
from .types import (Action, CopPlan, DFS_LOC, FileSpec, NodeState, StartCop,
                    StartTask, TaskSpec, Transfer)

__all__ = [
    "ADAPTER_API", "Action", "AssignmentProblem",
    "CopPlan", "CwsAdapter", "DFS_LOC", "DataPlacementService", "FileSpec",
    "FingerprintCache", "IncrementalAssignmentSolver",
    "NodeCapacityArray", "NodeOrder", "NodeState", "OrigAdapter", "ReadySet",
    "ReferenceWowScheduler", "RuntimeAdapter", "ShapeIndex", "StartCop",
    "StartTask", "TaskSpec", "Transfer", "WowAdapter", "WowScheduler",
    "abstract_ranks", "assert_implements", "assign_priorities",
    "component_fingerprint", "decompose", "make_adapter",
    "priority_value", "solve", "solve_exact", "solve_greedy",
    "solve_monolithic",
]
