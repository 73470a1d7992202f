"""Core: the paper's contribution — data-flow scheduling with affinity.

Exports the task-graph model, machine/performance models, the XKaapi-like
simulator, and the scheduling strategies (HEFT, DADA, dual approximation,
work stealing).
"""
from .affinity import AFFINITY_FUNCTIONS, AFFINITY_MATRIX_FUNCTIONS
from .api import (
    BatchResult,
    Summary,
    cached_graph,
    default_jobs,
    get_pool,
    make_strategy,
    pool_allowed,
    run_batch,
    run_many,
    run_simulation,
)
from .backend import backend_name, get_backend
from .dada import DADA, DualApprox
from .dag import Access, DataObject, GraphArrays, Mode, Task, TaskGraph
from .heft import HEFT
from .machine import (
    HOST_MEM,
    LinkModel,
    MachineModel,
    Resource,
    ResourceClass,
    make_machine,
)
from .perfmodel import ClassPredictor, HistoryPerfModel, Residency, TransferModel
from .simulator import SimResult, Simulator, Strategy

# WorkSteal is the queue protocol itself and lives with it in the layered
# runtime (repro.runtime.queues); re-exported here unchanged
from repro.runtime.queues import WorkSteal

# importing the policy package last (it imports the strategy classes
# above) registers the built-in policies and attaches the score_matrix
# views, so `HEFT().score_matrix` / `repro.sched.resolve` work however
# the packages are first imported
from repro import sched as _sched  # noqa: E402  (deliberate tail import)

__all__ = [
    "AFFINITY_FUNCTIONS", "AFFINITY_MATRIX_FUNCTIONS", "Access", "BatchResult",
    "ClassPredictor", "DADA", "DataObject", "DualApprox", "GraphArrays",
    "HEFT", "HOST_MEM", "HistoryPerfModel", "LinkModel", "MachineModel",
    "Mode", "Residency", "Resource", "ResourceClass", "SimResult",
    "Simulator", "Strategy", "Summary", "Task", "TaskGraph", "TransferModel",
    "WorkSteal", "backend_name", "cached_graph", "default_jobs", "get_backend",
    "get_pool", "make_machine", "make_strategy", "pool_allowed", "run_batch",
    "run_many", "run_simulation",
]
