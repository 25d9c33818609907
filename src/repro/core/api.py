"""One typed planning facade: ``plan(PlanRequest) -> PlanResult``.

The planner entry points accreted knobs over five PRs --
``swot_schedule`` (method / mode / milp_time_limit / plane_ready /
bypass_depth), ``swot_greedy_chain`` (rollout_horizon /
max_enumerated_planes / polish), ``swot_greedy_grid`` / ``plan_grid``
(backend / planner / independent_split / attribution).  This module
consolidates them behind one frozen, validated options record:

* ``PlannerOptions`` -- every knob, with documented defaults identical
  to the historical per-function defaults;
* ``PlanRequest`` -- the work: one or many (fabric, pattern) cells,
  plus per-plane ready offsets for the single-cell (arbiter re-plan)
  case;
* ``plan()`` -- dispatches exactly as the legacy entry points did, so
  outputs are bitwise-identical (parity-tested in tests/test_trace.py).
  The legacy functions survive as thin delegates.

Dispatch rules (the same policy the legacy functions implemented):

* one cell -> the per-instance path: ``auto`` hands to the exact MILP
  while ``2 * steps * planes <= 70`` binaries, else the greedy;
  ``milp`` runs both and keeps the realized faster schedule;
  ``greedy`` runs the reserve-set greedy (CHAIN) or
  best-of-packing-and-chain (INDEPENDENT); ``strawman`` executes the
  lockstep reconfigure-then-transmit baseline (every plane serves every
  step -- the "no intra-collective reconfiguration overlap" arm the
  model-trace replay compares against).
* many cells -> the instance-batched grid path (``swot_greedy_grid`` +
  one batched strawman scoring pass), backend/planner chosen by grid
  size, mode and platform (`repro.core.ir.backends.route`).

New call sites (the `repro.trace` replay path, benchmarks) use only this
facade.
"""

from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Sequence

from repro.core.baselines import strawman_decisions, strawman_instance
from repro.core.fabric import OpticalFabric
from repro.core.greedy import (
    GridPlan,
    _routed_grid,
    swot_greedy_chain,
    swot_greedy_independent,
)
from repro.core.ir import batch_evaluate, route
from repro.core.milp import solve_milp
from repro.core.patterns import Pattern
from repro.core.schedule import DependencyMode, Schedule
from repro.core.simulator import execute
from repro.obs.trace import host_span, request_scope

if TYPE_CHECKING:
    from repro.core.ir.backends import TimingBackend

# Above this many (step, plane) binaries the MILP hands over to the
# greedy (+ LP-polished structure local search), which empirically
# dominates HiGHS branch-and-cut beyond this size within any reasonable
# time limit.  (Moved here from `repro.core.scheduler`, which re-exports
# it.)
_MILP_BINARY_BUDGET = 70

_METHODS = ("auto", "milp", "greedy", "strawman")
_GRID_METHODS = ("auto", "greedy")


@dataclasses.dataclass(frozen=True)
class PlannerOptions:
    """Every planning knob, validated, with the historical defaults.

    ================== ======================================== =========
    field              consolidates (legacy entry point)        default
    ================== ======================================== =========
    method             ``swot_schedule(method=)``               "auto"
    mode               ``swot_schedule``/``plan_grid(mode=)``   CHAIN
    backend            ``plan_grid``/``swot_greedy_grid``       None
    planner            ``plan_grid(planner=)`` step|fused       None
    bypass_depth       every entry point                        0
    independent_split  ``plan_grid(independent_split=)``        False
    polish             ``swot_greedy_chain(polish=)``           True
    rollout_horizon    ``swot_greedy_chain(rollout_horizon=)``  24
    max_enum_planes    ``swot_greedy_chain`` enumeration cap    8
    milp_time_limit    ``swot_schedule(milp_time_limit=)``      30.0
    attribution        ``plan_grid(attribution=)``              False
    ================== ======================================== =========

    ``backend=None`` / ``planner=None`` take the grid's route
    (`repro.core.ir.backends.route`); ``method="strawman"`` is new with
    the facade -- the lockstep-ICR baseline as a first-class method, so
    replay paths can toggle reconfiguration overlap off per job.
    """

    method: str = "auto"
    mode: DependencyMode = DependencyMode.CHAIN
    backend: "str | TimingBackend | None" = None
    planner: str | None = None
    bypass_depth: int = 0
    independent_split: bool = False
    polish: bool = True
    rollout_horizon: int = 24
    max_enumerated_planes: int = 8
    milp_time_limit: float = 30.0
    attribution: bool = False

    def __post_init__(self) -> None:
        if self.method not in _METHODS:
            raise ValueError(
                f"method must be one of {_METHODS}, got {self.method!r}"
            )
        if not isinstance(self.mode, DependencyMode):
            raise ValueError(
                f"mode must be a DependencyMode, got {self.mode!r}"
            )
        if self.planner not in (None, "step", "fused"):
            raise ValueError(
                "planner must be None, 'step' or 'fused', got "
                f"{self.planner!r}"
            )
        if self.bypass_depth != 0 and self.bypass_depth < 2:
            raise ValueError(
                "bypass_depth is 0 (off) or >= 2 (relay hop budget), "
                f"got {self.bypass_depth}"
            )
        if (
            self.independent_split
            and self.mode is not DependencyMode.INDEPENDENT
        ):
            raise ValueError(
                "independent_split requires mode=INDEPENDENT "
                "(water-fill splitting has no CHAIN analogue)"
            )
        if self.rollout_horizon < 1:
            raise ValueError("rollout_horizon must be >= 1")
        if self.max_enumerated_planes < 1:
            raise ValueError("max_enumerated_planes must be >= 1")
        if self.milp_time_limit <= 0:
            raise ValueError("milp_time_limit must be positive")


@dataclasses.dataclass(frozen=True)
class PlanRequest:
    """The work to plan: one or many (fabric, pattern) cells.

    ``batched=None`` (the default) picks the path by cell count -- one
    cell plans per-instance, several plan through the batched grid.
    ``batched=True`` forces the grid path even for one cell (a sweep of
    size one still wants `GridCellPlan` scoring); ``batched=False``
    forces per-instance planning and requires exactly one cell.
    """

    cells: tuple[tuple[OpticalFabric, Pattern], ...]
    plane_ready: tuple[float, ...] | None = None
    options: PlannerOptions = PlannerOptions()
    batched: bool | None = None

    @classmethod
    def single(
        cls,
        fabric: OpticalFabric,
        pattern: Pattern,
        *,
        plane_ready: Sequence[float] | None = None,
        options: PlannerOptions | None = None,
    ) -> "PlanRequest":
        return cls(
            cells=((fabric, pattern),),
            plane_ready=(
                tuple(plane_ready) if plane_ready is not None else None
            ),
            options=options or PlannerOptions(),
            batched=False,
        )

    @classmethod
    def grid(
        cls,
        cells: Sequence[tuple[OpticalFabric, Pattern]],
        *,
        options: PlannerOptions | None = None,
    ) -> "PlanRequest":
        return cls(
            cells=tuple(cells),
            options=options or PlannerOptions(),
            batched=True,
        )

    @property
    def is_batched(self) -> bool:
        if self.batched is not None:
            return self.batched
        return len(self.cells) > 1

    def __post_init__(self) -> None:
        if not self.cells:
            raise ValueError("PlanRequest needs at least one cell")
        if self.batched is False and len(self.cells) != 1:
            raise ValueError(
                "batched=False (per-instance planning) takes exactly "
                "one cell"
            )
        if self.plane_ready is not None and self.is_batched:
            raise ValueError(
                "plane_ready applies to per-instance requests only "
                "(the arbiter's staggered-lease re-plan case)"
            )


@dataclasses.dataclass(frozen=True)
class GridCellPlan:
    """One sweep cell planned by the grid path: greedy plan + baseline.

    (Moved here from `repro.core.scheduler`, which re-exports it.)
    """

    plan: GridPlan
    strawman_cct: float

    @property
    def cct(self) -> float:
        return self.plan.cct

    @property
    def vs_strawman(self) -> float | None:
        if self.strawman_cct == 0:
            return None
        return 1.0 - self.plan.cct / self.strawman_cct


@dataclasses.dataclass(frozen=True)
class PlanResult:
    """What ``plan()`` produced, one entry per request cell.

    ``schedules`` is populated on the per-instance path; the grid path
    returns ``grid`` (decisions + scores) and materializes activity
    objects lazily via ``schedule(i)``.
    """

    options: PlannerOptions
    methods: tuple[str, ...]  # planner that produced each cell
    ccts: tuple[float, ...]
    schedules: tuple[Schedule, ...] | None = None
    grid: tuple[GridCellPlan, ...] | None = None

    def schedule(self, i: int = 0) -> Schedule:
        """The cell's schedule (materialized from decisions on the grid
        path)."""
        if self.schedules is not None:
            return self.schedules[i]
        assert self.grid is not None
        return self.grid[i].plan.schedule()

    @property
    def cct(self) -> float:
        """Single-cell convenience accessor."""
        if len(self.ccts) != 1:
            raise ValueError(
                f"result holds {len(self.ccts)} cells; use .ccts"
            )
        return self.ccts[0]

    @property
    def method(self) -> str:
        if len(self.methods) != 1:
            raise ValueError(
                f"result holds {len(self.methods)} cells; use .methods"
            )
        return self.methods[0]


def _plan_single(
    fabric: OpticalFabric,
    pattern: Pattern,
    plane_ready: tuple[float, ...] | None,
    opts: PlannerOptions,
) -> tuple[Schedule, str]:
    """The per-instance dispatch (the historical ``swot_schedule`` body,
    plus the ``strawman`` method)."""

    def greedy() -> Schedule:
        chain = swot_greedy_chain(
            fabric,
            pattern,
            rollout_horizon=opts.rollout_horizon,
            max_enumerated_planes=opts.max_enumerated_planes,
            polish=opts.polish,
            plane_ready=plane_ready,
            bypass_depth=opts.bypass_depth,
        )
        if opts.mode is DependencyMode.CHAIN:
            return chain
        # Every CHAIN-legal schedule is INDEPENDENT-legal (the barrier is
        # just conservative): independent mode keeps the better of
        # step-packing and the chain scheduler.
        indep = swot_greedy_independent(
            fabric, pattern, polish=opts.polish, plane_ready=plane_ready
        )
        return chain if chain.cct < indep.cct else indep

    method = opts.method
    if method == "strawman":
        return (
            execute(
                fabric,
                pattern,
                strawman_decisions(fabric, pattern),
                plane_ready=plane_ready,
            ),
            "strawman",
        )
    if method == "auto":
        n_bin = 2 * pattern.n_steps * fabric.n_planes
        method = "milp" if n_bin <= _MILP_BINARY_BUDGET else "greedy"
    if method == "milp":
        greedy_schedule = greedy()
        try:
            milp_schedule = solve_milp(
                fabric,
                pattern,
                mode=opts.mode,
                time_limit=opts.milp_time_limit,
                plane_ready=plane_ready,
            ).schedule
        except RuntimeError:
            return greedy_schedule, "greedy"  # solver hiccup: greedy+LP
        # The greedy occasionally matches MILP under a solver time limit
        # (or beats it via bypass relays the MILP cannot model); keep
        # whichever realized schedule is faster.
        if greedy_schedule.cct < milp_schedule.cct:
            return greedy_schedule, "greedy"
        return milp_schedule, "milp"
    assert method == "greedy"
    return greedy(), "greedy"


def _plan_grid(
    cells: tuple[tuple[OpticalFabric, Pattern], ...],
    opts: PlannerOptions,
) -> tuple[GridCellPlan, ...]:
    """The instance-batched dispatch (the historical ``plan_grid`` body)."""
    if opts.method not in _GRID_METHODS:
        raise ValueError(
            f"grid requests support method in {_GRID_METHODS}, got "
            f"{opts.method!r} (plan cells one at a time for "
            "milp/strawman)"
        )
    taken = route(
        len(cells),
        chain=opts.mode is DependencyMode.CHAIN,
        planner=opts.planner,
        backend=opts.backend,
    )
    plans = _routed_grid(
        cells,
        taken,
        opts.rollout_horizon,
        opts.max_enumerated_planes,
        opts.mode,
        opts.bypass_depth,
        opts.independent_split,
        opts.attribution,
    )
    with host_span("repro.grid.baseline", cells=len(cells)):
        baseline = [
            strawman_instance(fabric, pattern) for fabric, pattern in cells
        ]
    straw = batch_evaluate(baseline, backend=taken.backend)
    return tuple(
        GridCellPlan(plan=plan, strawman_cct=float(straw.cct[i]))
        for i, plan in enumerate(plans)
    )


def plan(request: PlanRequest) -> PlanResult:
    """Plan every cell of ``request`` under its ``PlannerOptions``.

    One cell routes through the per-instance path (exact MILP when
    tractable, LP-polished greedy at scale, or the strawman baseline);
    many cells route through the instance-batched grid path.  Outputs
    are bitwise-identical to the legacy entry points these paths were
    lifted from (``swot_schedule`` / ``plan_grid``), which now delegate
    here.

    Each call runs under its own ``request_scope``, so the host spans it
    opens (`repro.obs.trace.host_span`) share one ``request`` number.
    """
    with request_scope():
        return _plan(request)


def _plan(request: PlanRequest) -> PlanResult:
    opts = request.options
    if not request.is_batched:
        fabric, pattern = request.cells[0]
        schedule, used = _plan_single(
            fabric, pattern, request.plane_ready, opts
        )
        return PlanResult(
            options=opts,
            methods=(used,),
            ccts=(schedule.cct,),
            schedules=(schedule,),
        )
    grid = _plan_grid(request.cells, opts)
    return PlanResult(
        options=opts,
        methods=("greedy",) * len(grid),
        ccts=tuple(cell.cct for cell in grid),
        grid=grid,
    )
