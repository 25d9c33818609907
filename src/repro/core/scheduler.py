"""SWOT scheduler facade: exact MILP when tractable, greedy at scale.

The dispatch policy now lives in `repro.core.api` behind the unified
``plan(PlanRequest) -> PlanResult`` entry point; the functions here are
thin, signature-stable delegates kept for existing call sites (the
runtime arbiter, benchmarks, examples).  ``swot_schedule(...)`` and
``plan_grid(...)`` produce bitwise-identical outputs to their
pre-facade implementations (parity-tested in tests/test_trace.py).

``plan_grid`` is the sweep-scale entry point: a whole grid of (fabric,
pattern) cells is planned by the instance-batched greedy
(`repro.core.greedy.swot_greedy_grid`) and scored -- including the
strawman baseline for every cell -- in two ``batch_evaluate`` passes on
the selected IR backend (numpy / jax / pallas).
"""

from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Sequence

from repro.core.api import (  # noqa: F401  (compat re-exports)
    _MILP_BINARY_BUDGET,
    GridCellPlan,
    PlannerOptions,
    PlanRequest,
    plan,
)
from repro.core.baselines import (
    InfeasibleError,
    ideal_cct,
    one_shot_cct,
    strawman_cct,
)
from repro.core.fabric import OpticalFabric
from repro.core.patterns import Pattern
from repro.core.schedule import DependencyMode, Schedule

if TYPE_CHECKING:
    from repro.core.ir.backends import TimingBackend


@dataclasses.dataclass(frozen=True)
class SwotPlan:
    """A scheduled collective plus the baselines it is compared against."""

    pattern: Pattern
    fabric: OpticalFabric
    schedule: Schedule
    method: str  # "milp" | "greedy"
    cct: float
    strawman_cct: float | None
    one_shot_cct: float | None  # None when one-shot is infeasible
    ideal_cct: float

    @property
    def vs_strawman(self) -> float | None:
        if self.strawman_cct is None or self.strawman_cct == 0:
            return None
        return 1.0 - self.cct / self.strawman_cct

    @property
    def vs_one_shot(self) -> float | None:
        if self.one_shot_cct is None or self.one_shot_cct == 0:
            return None
        return 1.0 - self.cct / self.one_shot_cct


def swot_schedule(
    fabric: OpticalFabric,
    pattern: Pattern,
    method: str = "auto",
    mode: DependencyMode = DependencyMode.CHAIN,
    milp_time_limit: float = 30.0,
    plane_ready: Sequence[float] | None = None,
    bypass_depth: int = 0,
) -> tuple[Schedule, str]:
    """Schedule ``pattern`` on ``fabric`` with SWOT overlap optimization.

    Delegates to ``repro.core.api.plan``; see `PlannerOptions` for the
    knob semantics.  ``plane_ready`` gives per-plane earliest activity
    times (the arbiter's staggered-lease case); ``bypass_depth >= 2``
    enables Topology-Bypassing relay candidates; ``method="strawman"``
    executes the lockstep reconfigure-then-transmit baseline.
    """
    result = plan(
        PlanRequest.single(
            fabric,
            pattern,
            plane_ready=plane_ready,
            options=PlannerOptions(
                method=method,
                mode=mode,
                milp_time_limit=milp_time_limit,
                bypass_depth=bypass_depth,
            ),
        )
    )
    return result.schedule(), result.method


def plan_collective(
    fabric: OpticalFabric,
    pattern: Pattern,
    method: str = "auto",
    mode: DependencyMode = DependencyMode.CHAIN,
    one_shot_planes: int | None = None,
    milp_time_limit: float = 30.0,
) -> SwotPlan:
    """Produce the full SWOT plan incl. baseline CCTs for one collective."""
    schedule, used = swot_schedule(
        fabric, pattern, method=method, mode=mode,
        milp_time_limit=milp_time_limit,
    )
    # Baseline CCTs come from the array IR (no activity-object builds).
    try:
        oneshot: float | None = one_shot_cct(
            fabric, pattern, n_planes=one_shot_planes
        )
    except InfeasibleError:
        oneshot = None
    return SwotPlan(
        pattern=pattern,
        fabric=fabric,
        schedule=schedule,
        method=used,
        cct=schedule.cct,
        strawman_cct=strawman_cct(fabric, pattern),
        one_shot_cct=oneshot,
        ideal_cct=ideal_cct(fabric, pattern),
    )


def plan_grid(
    cells: Sequence[tuple[OpticalFabric, Pattern]],
    backend: "str | TimingBackend | None" = None,
    rollout_horizon: int = 24,
    mode: DependencyMode = DependencyMode.CHAIN,
    bypass_depth: int = 0,
    independent_split: bool = False,
    planner: str | None = None,
    attribution: bool = False,
) -> list[GridCellPlan]:
    """Plan a whole sweep grid in one instance-batched pass.

    Delegates to ``repro.core.api.plan``.  The batched greedy plans
    every (fabric, pattern) cell together (``swot_greedy_grid``), then
    ONE more ``batch_evaluate`` pass scores the strawman-ICR baseline
    for every cell -- both on the selected IR backend.  ``backend=None``
    auto-selects jax once the grid reaches
    ``REPRO_GRID_BACKEND_THRESHOLD`` cells (the arbiter's shared
    ``select_backend_by_size`` policy; else the ``REPRO_IR_BACKEND``
    env default), and an explicit ``backend`` always wins.  ``mode``
    picks the per-cell planner: CHAIN (paper-faithful reserve-set
    greedy, optionally with Topology-Bypassing relay candidates via
    ``bypass_depth >= 2``) or INDEPENDENT (least-finish-time step
    packing, or per-row-volume water-fill splitting with
    ``independent_split=True`` for plane-heterogeneous fabrics) --
    each bitwise-equal to its per-instance reference.  Use this for
    message-size x ``t_recfg`` x plane-count sweeps; for single
    collectives (or when LP polish matters) use ``plan_collective``.

    ``planner`` picks the loop implementation: ``"step"`` (per-step
    numpy), ``"fused"`` (the whole loop as one jitted ``lax.scan`` on
    device, `repro.core.ir.fused` -- bitwise-identical decisions), or
    ``None`` to auto-select by grid size, mode and device
    (`select_planner_by_size`).  ``attribution=True`` threads the per-cell CCT decomposition
    (`repro.obs.attribution.Attribution`) through the scoring pass onto
    each ``GridCellPlan.plan.attribution`` -- composes with both
    planners and every backend.
    """
    result = plan(
        PlanRequest.grid(
            cells,
            options=PlannerOptions(
                mode=mode,
                backend=backend,
                planner=planner,
                bypass_depth=bypass_depth,
                independent_split=independent_split,
                rollout_horizon=rollout_horizon,
                attribution=attribution,
            ),
        )
    )
    assert result.grid is not None
    return list(result.grid)
