"""SWOT core: intra-collective optical reconfiguration with overlap.

Public API for the paper's contribution:

* ``OpticalFabric`` -- p nodes x k OCS planes, bandwidth, reconfig latency.
* ``patterns`` -- CC algorithms as bijective-pairing step sequences.
* ``solve_milp`` / ``swot_greedy`` / ``swot_schedule`` -- the SWOT
  reconfiguration-communication overlap schedulers.
* ``one_shot`` / ``strawman_icr`` / ``ideal_cct`` -- the paper's baselines.
* ``SwotShim`` / ``OpticalController`` -- the coordination shim.
"""

from repro.core.api import (
    PlannerOptions,
    PlanRequest,
    PlanResult,
    plan,
)
from repro.core.baselines import (
    InfeasibleError,
    ideal_cct,
    one_shot,
    one_shot_allocation,
    one_shot_cct,
    prestage_for,
    strawman_cct,
    strawman_decisions,
    strawman_icr,
    strawman_instance,
)
from repro.core.bypass import (
    config_perms,
    enumerate_relay_routes,
    relay_depth_table,
)
from repro.core.fabric import (
    FIG5_LINK_BANDWIDTH,
    PAPER_LINK_BANDWIDTH,
    PAPER_RECONFIG_LATENCY,
    TPU_V5E_LINK_BANDWIDTH,
    OpticalFabric,
)
from repro.core.greedy import (
    GridPlan,
    independent_decisions,
    independent_split_decisions,
    swot_greedy,
    swot_greedy_grid,
)
from repro.core.ir import (
    BackendUnavailable,
    BatchInstance,
    BatchResult,
    IRMetrics,
    ScheduleIR,
    TimingBackend,
    batch_evaluate,
    evaluate_decisions,
    execute_ir,
    from_ir,
    get_backend,
    to_ir,
    validate_ir,
)
from repro.core.milp import MilpResult, solve_milp
from repro.core.patterns import (
    ALGORITHMS,
    Pattern,
    Step,
    all_gather,
    bruck_alltoall,
    get_pattern,
    neighbor_exchange,
    pairwise_alltoall,
    rabenseifner_allreduce,
    reduce_scatter,
    ring_allreduce,
)
from repro.core.schedule import (
    BypassRoute,
    Decisions,
    DependencyMode,
    Kind,
    PlaneActivity,
    Schedule,
)
from repro.core.scheduler import (
    GridCellPlan,
    SwotPlan,
    plan_collective,
    plan_grid,
    swot_schedule,
)
from repro.core.shim import CollectiveRequest, OpticalController, SwotShim
from repro.core.simulator import cct_of, execute

__all__ = [
    "ALGORITHMS",
    "BackendUnavailable",
    "BatchInstance",
    "BatchResult",
    "BypassRoute",
    "CollectiveRequest",
    "Decisions",
    "DependencyMode",
    "FIG5_LINK_BANDWIDTH",
    "GridCellPlan",
    "GridPlan",
    "IRMetrics",
    "InfeasibleError",
    "Kind",
    "MilpResult",
    "OpticalController",
    "OpticalFabric",
    "PAPER_LINK_BANDWIDTH",
    "PAPER_RECONFIG_LATENCY",
    "Pattern",
    "PlanRequest",
    "PlanResult",
    "PlaneActivity",
    "PlannerOptions",
    "Schedule",
    "ScheduleIR",
    "Step",
    "SwotPlan",
    "SwotShim",
    "TPU_V5E_LINK_BANDWIDTH",
    "TimingBackend",
    "all_gather",
    "batch_evaluate",
    "bruck_alltoall",
    "cct_of",
    "config_perms",
    "enumerate_relay_routes",
    "evaluate_decisions",
    "execute",
    "execute_ir",
    "from_ir",
    "get_backend",
    "get_pattern",
    "ideal_cct",
    "neighbor_exchange",
    "one_shot",
    "one_shot_allocation",
    "one_shot_cct",
    "pairwise_alltoall",
    "plan",
    "plan_collective",
    "plan_grid",
    "prestage_for",
    "rabenseifner_allreduce",
    "reduce_scatter",
    "relay_depth_table",
    "ring_allreduce",
    "solve_milp",
    "strawman_cct",
    "strawman_decisions",
    "strawman_icr",
    "strawman_instance",
    "independent_decisions",
    "independent_split_decisions",
    "swot_greedy",
    "swot_greedy_grid",
    "swot_schedule",
    "to_ir",
    "validate_ir",
]
