"""Scalable overlap-aware greedy scheduler (array-IR scoring engine).

The MILP (`repro.core.milp`) is exact but its solve time grows with steps x
planes; the paper reports ~90 s at 128 nodes with Gurobi.  This greedy
scheduler makes the same class of decisions -- per-step volume splits plus
"reserve a plane now so it can reconfigure for an upcoming config while the
others keep transmitting" -- in O(2^k S^2) time, which handles 512-node
collectives in milliseconds.  It is cross-validated against the MILP optimum
on every instance small enough to solve exactly (tests assert a small gap).

Candidate evaluation runs on the array IR (`repro.core.ir`): per step, every
candidate reserve set becomes one row of a (candidates x planes) state
batch, the step's water-filling split is solved for all candidates in one
``waterfill_batch`` call, and the remaining steps are scored with one
``rollout_batch`` call -- no per-candidate Python rollout loops.

CHAIN mode (paper-faithful):
  per step, enumerate which planes to *reserve* (divert to reconfigure for
  an upcoming config); the remaining planes carry the step's volume with
  water-filling splits (equalized finish times given per-plane ready
  times).  Candidates are scored by rolling out the remaining steps with
  the no-reserve policy and comparing final CCT.  With ``bypass_depth >=
  2``, every reserve-set candidate gains a Topology-Bypassing twin
  (`repro.core.bypass`): config-mismatched planes with an ``h``-hop
  self-composition relay serve over their installed circuit at ``bw / h``
  instead of paying ``t_recfg`` -- decisive when reconfiguration
  dominates step transmission time -- and the bypass plan is kept only on
  a strict CCT win over the no-bypass plan.

INDEPENDENT mode (beyond-paper, for collectives whose steps carry no data
dependency, e.g. pairwise all-to-all):
  steps are packed onto planes by least-finish-time, letting transmissions
  of different steps proceed concurrently on different planes; the global
  step barrier (P3) disappears and reconfigurations pipeline naturally.

Both entry points accept ``plane_ready`` -- per-plane earliest activity
times -- so the runtime arbiter can re-plan a job onto planes that free at
different instants instead of waiting for the latest one.

``swot_greedy_grid`` batches the greedy across sweep *instances*: a whole
grid of (fabric, pattern, t_recfg) cells advances through the per-step
loop together.  In CHAIN mode every cell's candidate reserve sets come
from a table precomputed at grid construction (`_GridState`) and are
stacked into one (rows x planes) state batch, so each step costs ONE
batched candidate construction, ONE ``waterfill_batch``, ONE rollout
call, and ONE instance-keyed lexsort for the entire grid -- no
per-instance Python inside the loop.  INDEPENDENT mode packs every
cell's step by least finish time in one batched argmin.  Final decisions
are scored in one ``batch_evaluate`` pass on the selected IR backend.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import TYPE_CHECKING, Sequence

import numpy as np

from repro.core.bypass import relay_depth_table
from repro.core.fabric import OpticalFabric
from repro.core.ir import (
    NO_CONFIG,
    _BIG,
    BatchInstance,
    batch_evaluate,
    evaluate_decisions,
    fabric_arrays,
    rollout_batch,
    waterfill_batch,
)
from repro.core.ir.backends import (
    DEFAULT_GRID_BACKEND_THRESHOLD,
    ENV_GRID_BACKEND_THRESHOLD,
    select_backend_by_size,
    select_planner_by_size,
)
from repro.core.patterns import Pattern
from repro.core.schedule import (
    BypassRoute,
    Decisions,
    DependencyMode,
    Schedule,
)
from repro.core.simulator import execute
from repro.core.tolerances import EPS as _EPS
from repro.core.tolerances import EPS_VOLUME as _EPS_VOLUME

if TYPE_CHECKING:
    from repro.core.ir.backends import TimingBackend


def _upcoming_targets(
    pattern: Pattern, start_step: int, held: set[int], n: int
) -> list[int]:
    """Next ``n`` distinct upcoming configs not already held/being prepared."""
    targets: list[int] = []
    seen = set(held)
    for i in range(start_step, pattern.n_steps):
        cfg = pattern.steps[i].config
        if cfg not in seen:
            targets.append(cfg)
            seen.add(cfg)
            if len(targets) == n:
                break
    return targets


def _initial_state(
    fabric: OpticalFabric, plane_ready: Sequence[float] | None
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(bandwidth, config, free) arrays for the fabric's starting state."""
    bw, config = fabric_arrays(fabric)
    if plane_ready is None:
        free = np.zeros(fabric.n_planes)
    else:
        free = np.array(plane_ready, dtype=np.float64)
    return bw, config.copy(), free


def _reserve_candidates(
    pattern: Pattern,
    step_idx: int,
    n_planes: int,
    config: np.ndarray,
    free: np.ndarray,
    t_recfg: float,
    max_enumerated_planes: int,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Candidate reserve-set states for one instance at one step.

    Returns ``(trial_cfg, trial_free, reserved_mask, valid)``, all with a
    leading candidate dimension.  Reserved planes are retargeted toward
    upcoming configs (soonest-free first).  The single source of the
    candidate policy: both the per-instance chain greedy and the
    instance-batched grid call this, which is what keeps their bitwise
    parity contract edit-proof.  ``config``/``free`` may be wider than
    ``n_planes`` (the grid path's padded rows); enumeration and
    retargeting only touch real planes, and padded entries hold
    ``NO_CONFIG`` so the held-set construction ignores them.
    """
    step_config = pattern.steps[step_idx].config
    if n_planes <= max_enumerated_planes:
        reserve_sets = [
            set(c)
            for size in range(n_planes)
            for c in itertools.combinations(range(n_planes), size)
        ]
    else:
        by_free = sorted(range(n_planes), key=lambda j: free[j])
        reserve_sets = [set(by_free[:size]) for size in range(4)]
    n_cand = len(reserve_sets)
    trial_cfg = np.repeat(config[None, :], n_cand, axis=0)
    trial_free = np.repeat(free[None, :], n_cand, axis=0)
    reserved_mask = np.zeros((n_cand, config.shape[0]), dtype=bool)
    valid = np.ones(n_cand, dtype=bool)
    for c_idx, reserved in enumerate(reserve_sets):
        if len(reserved) == n_planes:
            valid[c_idx] = False
            continue
        held = {int(c) for c in trial_cfg[c_idx] if c != NO_CONFIG}
        held.add(step_config)
        targets = _upcoming_targets(
            pattern, step_idx + 1, held, len(reserved)
        )
        # Ties on free time break by plane index (sorted() is stable over
        # the ascending base order) -- the same rule as a stable argsort,
        # which is what keeps the vectorized grid enumeration
        # (`_reserve_rows`) bitwise-identical to this reference.
        by_free_r = sorted(sorted(reserved), key=lambda j: trial_free[c_idx, j])
        for j, cfg_t in zip(by_free_r, targets):
            trial_free[c_idx, j] += t_recfg
            trial_cfg[c_idx, j] = cfg_t
        if reserved:
            reserved_mask[c_idx, sorted(reserved)] = True
    return trial_cfg, trial_free, reserved_mask, valid


def has_ready_offsets(plane_ready: Sequence[float] | None) -> bool:
    """True when any plane carries a positive ready-time offset.

    Since the MILP learned per-plane ready anchoring, the only decision
    left on this predicate is gating the LP-hungry structure local search
    (hundreds of LP solves) out of the arbiter's staggered-lease re-plans.
    """
    return plane_ready is not None and any(r > 0.0 for r in plane_ready)


def _chain_decisions(
    fabric: OpticalFabric,
    pattern: Pattern,
    rollout_horizon: int,
    max_enumerated_planes: int,
    plane_ready: Sequence[float] | None,
    depth_tab: np.ndarray | None = None,
) -> Decisions:
    """The CHAIN-mode per-step candidate loop, as discrete decisions.

    ``depth_tab`` (from `repro.core.bypass.relay_depth_table`) enables
    Topology-Bypassing candidates: every reserve-set row gains a twin in
    which non-reserved, config-mismatched planes with a self-composition
    relay of ``h`` hops serve the step over their *installed* circuit at
    effective bandwidth ``bw / h`` instead of paying ``t_recfg`` -- the
    same water-fill/rollout scoring decides between reconfiguring and
    relaying.  ``None`` reproduces the pre-bypass greedy bit-for-bit.
    """
    n_planes = fabric.n_planes
    t_recfg = fabric.t_recfg
    bw, config, free = _initial_state(fabric, plane_ready)
    # The executor installs configs *lazily* (a plane reconfigures only
    # when it next serves a direct step), so the planning state `config`
    # -- which accumulates speculative reserve retargets -- can run ahead
    # of what is physically installed.  Bypass relays ride the physical
    # state, so it is tracked separately.
    installed = config.copy()
    step_configs = np.asarray(pattern.configs, dtype=np.int64)
    step_volumes = np.asarray(pattern.volumes, dtype=np.float64)
    barrier = 0.0
    splits: list[dict[int, float]] = []
    bypass_steps: list[tuple[BypassRoute, ...]] = []
    with_bypass = depth_tab is not None

    for i, step in enumerate(pattern.steps):
        # Candidate reserve sets: reserved planes skip this step and
        # reconfigure toward upcoming configs instead, then are excluded
        # from this step's water-fill (one state row per candidate).
        trial_cfg, trial_free, reserved_mask, valid = _reserve_candidates(
            pattern, i, n_planes, config, free, t_recfg,
            max_enumerated_planes,
        )
        byp_h = np.zeros_like(trial_cfg)
        if with_bypass:
            # Bypass twin rows: per plane, the minimal self-relay depth
            # from its *installed* circuit toward this step's pairing
            # (0 = no relay).  Rows without any relayable plane stay
            # invalid twins, so the base row always wins ties (it
            # precedes in candidate order).
            c_max = depth_tab.shape[0]
            known = (installed >= 0) & (installed < c_max)
            plane_hops = np.where(
                known,
                depth_tab[np.clip(installed, 0, c_max - 1), step.config],
                0,
            )
            hops = np.where(
                reserved_mask | (trial_cfg == step.config),
                0,
                plane_hops[None, :],
            )
            trial_cfg = np.concatenate([trial_cfg, trial_cfg], axis=0)
            trial_free = np.concatenate([trial_free, trial_free], axis=0)
            reserved_mask = np.concatenate(
                [reserved_mask, reserved_mask], axis=0
            )
            valid = np.concatenate([valid, valid & hops.any(axis=1)])
            byp_h = np.concatenate([np.zeros_like(hops), hops], axis=0)
        n_cand = trial_cfg.shape[0]
        bypassing = byp_h >= 2

        extra = np.where(
            (trial_cfg == step.config) | bypassing, 0.0, t_recfg
        )
        ready = np.maximum(barrier, trial_free + extra)
        ready = np.where(reserved_mask, _BIG, ready)
        bw_eff = np.where(bypassing, bw / np.maximum(byp_h, 1), bw)
        level, split = waterfill_batch(ready, bw_eff, step.volume)
        if step.volume > _EPS:
            valid &= (split > 0.0).any(axis=1)
        assert np.any(valid), "no feasible reserve set"
        active = split > 0.0
        new_free = np.where(active, level[:, None], trial_free)
        # Relaying planes keep their installed config (that is the point
        # of bypassing); only direct serves install the step's config.
        new_cfg = np.where(active & ~bypassing, step.config, trial_cfg)
        scores = rollout_batch(
            bw,
            t_recfg,
            step_configs,
            step_volumes,
            new_cfg,
            new_free,
            level,
            i + 1,
            rollout_horizon,
        )
        scores = np.where(valid, scores, np.inf)
        level_key = np.where(valid, level, np.inf)
        # Min by (score, level, candidate order) -- the same rule as the
        # historical first-strictly-better scan.  Scores can differ from
        # the interpreted rollout at ulp level (closed-form water level vs
        # iterative accumulation), so near-tied candidates may resolve
        # differently; schedule quality is pinned by the MILP
        # cross-validation tests, not by bitwise decision equality.
        best = int(np.lexsort((np.arange(n_cand), level_key, scores))[0])
        config = new_cfg[best]
        free = new_free[best]
        barrier = float(level[best])
        row_byp = byp_h[best]
        # Physically-installed state: direct serves install the step's
        # config (the executor's lazy reconfiguration); bypass relays and
        # reserve retargets leave it untouched.  The EPS_VOLUME threshold
        # mirrors the executor's idle-split filter, so this tracks what
        # the executor actually installs.
        installed = np.where(
            (split[best] > _EPS_VOLUME) & ~bypassing[best],
            step.config,
            installed,
        )
        splits.append(
            {
                j: float(split[best, j])
                for j in range(n_planes)
                if split[best, j] > 0.0 and row_byp[j] < 2
            }
        )
        bypass_steps.append(
            tuple(
                BypassRoute(
                    planes=(j,) * int(row_byp[j]),
                    volume=float(split[best, j]),
                )
                for j in range(n_planes)
                if split[best, j] > 0.0 and row_byp[j] >= 2
            )
        )

    return Decisions(
        tuple(splits),
        bypass=tuple(bypass_steps) if with_bypass else None,
    )


def swot_greedy_chain(
    fabric: OpticalFabric,
    pattern: Pattern,
    rollout_horizon: int = 24,
    max_enumerated_planes: int = 8,
    polish: bool = True,
    plane_ready: Sequence[float] | None = None,
    bypass_depth: int = 0,
) -> Schedule:
    """Greedy CHAIN-mode (paper-faithful P3) scheduler.

    ``bypass_depth >= 2`` additionally plans a Topology-Bypassing variant
    (relay candidates up to that many hops, `repro.core.bypass`) and
    keeps it only when its CCT strictly beats the no-bypass schedule --
    so enabling bypassing never hurts.  Bypass-winning schedules skip LP
    polish (the LP models reconfigure-then-transmit structures only).
    """
    decisions = _chain_decisions(
        fabric, pattern, rollout_horizon, max_enumerated_planes,
        plane_ready,
    )
    schedule = execute(
        fabric, pattern, decisions, plane_ready=plane_ready
    )
    # The fixed-structure LP anchors plane chains at their ready offsets,
    # so polish applies to staggered-lease re-plans too; the (much more
    # LP-hungry) structure local search stays gated to fresh fabrics.
    if polish:
        from repro.core.milp import lp_polish

        schedule = lp_polish(schedule, plane_ready=plane_ready)
        if not has_ready_offsets(plane_ready):
            schedule = _structure_local_search(fabric, pattern, schedule)
    if bypass_depth >= 2:
        depth_tab = relay_depth_table(pattern, bypass_depth)
        if depth_tab.any():
            byp = _chain_decisions(
                fabric, pattern, rollout_horizon, max_enumerated_planes,
                plane_ready, depth_tab,
            )
            # Guarded pick: replace only on a strict CCT win (scored on
            # the deterministic numpy backend, bitwise-equal to the
            # object executor) so bypass-enabled never regresses.
            if byp.bypass is not None and any(byp.bypass):
                byp_cct = evaluate_decisions(
                    fabric, pattern, byp, plane_ready=plane_ready,
                    backend="numpy",
                ).cct
                if byp_cct < schedule.cct:
                    schedule = execute(
                        fabric, pattern, byp, plane_ready=plane_ready
                    )
    return schedule


# Structure local search is gated to instances whose LP solves quickly.
_LOCAL_SEARCH_MAX_CELLS = 160
_LOCAL_SEARCH_MAX_LP = 400


def _structure_local_search(
    fabric: OpticalFabric, pattern: Pattern, schedule: Schedule
) -> Schedule:
    """Hill-climb the serving-set structure, scoring flips with the exact LP.

    The discrete structure of a SWOT schedule is fully captured by the
    serving sets ``u`` (reconfigurations follow lazily, and the LP recovers
    optimal continuous splits/timing for any ``u``).  Single-cell flips of
    ``u`` therefore explore structures the constructive greedy cannot
    reach, e.g. "both planes serve step 0 but one releases early".
    """
    from repro.core.milp import _structure_of, solve_fixed_structure

    n_cells = pattern.n_steps * fabric.n_planes
    if n_cells > _LOCAL_SEARCH_MAX_CELLS:
        return schedule
    u = _structure_of(schedule)["u"]
    best = schedule
    lp_calls = 0
    improved = True
    while improved and lp_calls < _LOCAL_SEARCH_MAX_LP:
        improved = False
        for i in range(pattern.n_steps):
            for j in range(fabric.n_planes):
                trial = u.copy()
                trial[i, j] = 1 - trial[i, j]
                if trial[i].sum() < 1:
                    continue
                cand = solve_fixed_structure(
                    fabric, pattern, trial, mode=schedule.mode,
                    validate=False,
                )
                lp_calls += 1
                if cand is not None and cand.cct < best.cct * (1 - 1e-9):
                    best, u = cand, trial
                    improved = True
                if lp_calls >= _LOCAL_SEARCH_MAX_LP:
                    break
            if lp_calls >= _LOCAL_SEARCH_MAX_LP:
                break
    if best is not schedule:
        # Candidates skip the per-solve legality re-check; re-validate
        # only the winner that escapes the search.
        best.validate()
    return best


def swot_greedy_chain_batch(
    cells: Sequence[tuple[OpticalFabric, Pattern]],
    rollout_horizon: int = 24,
    max_enumerated_planes: int = 8,
    plane_ready: Sequence[Sequence[float] | None] | None = None,
) -> list[Schedule]:
    """Plan many CHAIN cells through ONE instance-batched decisions pass.

    The runtime arbiter's batched-grant path: all jobs granted leases at
    one timestamp become one grid, their reserve-set decisions advance
    through the per-step loop together (``_chain_grid_chosen``, or the
    fused ``lax.scan`` planner once the batch crosses
    ``REPRO_FUSED_PLANNER_THRESHOLD``), and each cell is then
    materialized + polished exactly as ``swot_greedy_chain(polish=True)``
    would.  Because grid decisions are bitwise-identical to the
    per-instance greedy (the property the grid planners are pinned to),
    cell ``i``'s returned schedule is bitwise-identical to
    ``swot_greedy_chain(*cells[i], plane_ready=plane_ready[i])``.  That
    holds on IEEE-float64 platforms only: once the batch takes the fused
    planner on a TPU, whose float64 is emulated, a cell's schedule can
    depend on how many jobs were granted with it (ROADMAP speed item 3).

    ``plane_ready`` entries must carry no positive offsets
    (``has_ready_offsets`` false): the grid planner models fresh planes
    only.  Callers with staggered leases use the per-instance path.
    """
    if not cells:
        return []
    readies = (
        [None] * len(cells) if plane_ready is None else list(plane_ready)
    )
    assert len(readies) == len(cells)
    assert not any(has_ready_offsets(r) for r in readies), (
        "batched chain planning requires zero ready offsets"
    )
    planner = select_planner_by_size(len(cells), explicit=None)
    st = _GridState(
        cells,
        mode=DependencyMode.CHAIN,
        max_enumerated_planes=max_enumerated_planes,
    )
    decisions = _chain_grid_decisions(st, rollout_horizon, planner)
    from repro.core.milp import lp_polish

    schedules: list[Schedule] = []
    for (fabric, pattern), dec, ready in zip(cells, decisions, readies):
        # Identical epilogue to swot_greedy_chain(polish=True) with the
        # caller's (zero-offset) plane_ready threaded through, so the LP
        # solves the same program the per-instance path would.
        schedule = execute(fabric, pattern, dec, plane_ready=ready)
        schedule = lp_polish(schedule, plane_ready=ready)
        schedule = _structure_local_search(fabric, pattern, schedule)
        schedules.append(schedule)
    return schedules


def independent_decisions(
    fabric: OpticalFabric,
    pattern: Pattern,
    plane_ready: Sequence[float] | None = None,
) -> Decisions:
    """Least-finish-time INDEPENDENT-mode packing decisions (one instance).

    The single-instance reference the instance-batched grid path
    (`swot_greedy_grid(mode=INDEPENDENT)`) is bitwise-pinned against.
    """
    bw, config, free = _initial_state(fabric, plane_ready)
    splits: list[dict[int, float]] = []
    for step in pattern.steps:
        # Finish time if the whole step lands on plane j.
        extra = np.where(config == step.config, 0.0, fabric.t_recfg)
        finish = free + extra + step.volume / bw
        j = int(np.argmin(finish))
        free[j] = finish[j]
        config[j] = step.config
        splits.append({j: step.volume})
    return Decisions(tuple(splits), mode=DependencyMode.INDEPENDENT)


def swot_greedy_independent(
    fabric: OpticalFabric,
    pattern: Pattern,
    polish: bool = True,
    plane_ready: Sequence[float] | None = None,
) -> Schedule:
    """Beyond-paper INDEPENDENT-mode packing (no cross-step barrier)."""
    schedule = execute(
        fabric,
        pattern,
        independent_decisions(fabric, pattern, plane_ready),
        plane_ready=plane_ready,
    )
    if polish:
        from repro.core.milp import lp_polish

        schedule = lp_polish(schedule, plane_ready=plane_ready)
    return schedule


def independent_split_decisions(
    fabric: OpticalFabric,
    pattern: Pattern,
    plane_ready: Sequence[float] | None = None,
) -> Decisions:
    """Water-filled INDEPENDENT-mode decisions (one instance).

    Each step's volume splits across ALL planes with equalized finish
    times -- the plane-heterogeneous alternative to the argmin packing of
    ``independent_decisions``: straggler planes (bandwidth scale < 1)
    absorb proportionally less instead of stalling a whole step.  The
    single-instance reference the instance-batched grid path
    (``swot_greedy_grid(mode=INDEPENDENT, independent_split=True)``) is
    bitwise-pinned against.
    """
    bw, config, free = _initial_state(fabric, plane_ready)
    splits: list[dict[int, float]] = []
    for step in pattern.steps:
        extra = np.where(config == step.config, 0.0, fabric.t_recfg)
        ready = (free + extra)[None, :]
        level, split = waterfill_batch(ready, bw, step.volume)
        active = split[0] > 0.0
        free = np.where(active, level[0], free)
        config = np.where(active, step.config, config)
        splits.append(
            {
                j: float(split[0, j])
                for j in range(fabric.n_planes)
                if split[0, j] > 0.0
            }
        )
    return Decisions(tuple(splits), mode=DependencyMode.INDEPENDENT)


def swot_greedy(
    fabric: OpticalFabric,
    pattern: Pattern,
    mode: DependencyMode = DependencyMode.CHAIN,
    plane_ready: Sequence[float] | None = None,
    bypass_depth: int = 0,
) -> Schedule:
    if mode is DependencyMode.CHAIN:
        return swot_greedy_chain(
            fabric, pattern, plane_ready=plane_ready,
            bypass_depth=bypass_depth,
        )
    # Every CHAIN-legal schedule is INDEPENDENT-legal (the barrier is just
    # conservative), so independent mode returns the better of step-packing
    # and the chain scheduler -- splitting wins when steps are few or wide.
    indep = swot_greedy_independent(fabric, pattern, plane_ready=plane_ready)
    chain = swot_greedy_chain(
        fabric, pattern, plane_ready=plane_ready, bypass_depth=bypass_depth
    )
    return chain if chain.cct < indep.cct else indep


# ---------------------------------------------------------------------------
# Instance-batched greedy: plan a whole sweep grid in one batched pass
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class GridPlan:
    """One cell's outcome from ``swot_greedy_grid``."""

    fabric: OpticalFabric
    pattern: Pattern
    decisions: Decisions
    cct: float
    n_reconfigurations: int
    utilization: float
    # Per-cell CCT decomposition (``attribution=True`` only): an
    # `repro.obs.attribution.Attribution` with (S, P) component arrays
    # sliced from the batch scoring pass -- identical for the step and
    # fused planners, since their decisions are bitwise-equal.
    attribution: "object | None" = None

    def schedule(self) -> Schedule:
        """Materialize the activity-object schedule (validated)."""
        return execute(self.fabric, self.pattern, self.decisions)


class _GridState:
    """Packed per-instance planner state for the instance-batched greedy.

    CHAIN mode additionally precomputes the *candidate reserve-set table*:
    one flat row per (instance, reserve set) in exactly the enumeration
    order of ``_reserve_candidates`` (subset enumeration when
    ``n_planes <= max_enumerated_planes``, soonest-free prefixes of sizes
    0..3 otherwise), plus the ``prev_same`` first-occurrence table that
    lets upcoming-target retargeting run as array ops.  The per-step loop
    then touches no per-instance Python at all: candidate construction,
    water-filling, rollout scoring, and selection are each ONE batched
    call over every candidate row of every live instance.
    """

    def __init__(
        self,
        cells: Sequence[tuple[OpticalFabric, Pattern]],
        mode: DependencyMode = DependencyMode.CHAIN,
        max_enumerated_planes: int = 8,
        bypass_depth: int = 0,
    ):
        b = len(cells)
        self.cells = list(cells)
        self.mode = mode
        self.max_enumerated_planes = max_enumerated_planes
        self.bypass_depth = bypass_depth
        self.n_p = np.array(
            [f.n_planes for f, _ in cells], dtype=np.int64
        )
        self.n_s = np.array(
            [p.n_steps for _, p in cells], dtype=np.int64
        )
        p_max = int(self.n_p.max())
        s_max = int(self.n_s.max())
        self.p_max, self.s_max = p_max, s_max
        self.bw = np.ones((b, p_max))
        self.config = np.full((b, p_max), NO_CONFIG, dtype=np.int64)
        self.free = np.zeros((b, p_max))
        self.barrier = np.zeros(b)
        self.real = np.zeros((b, p_max), dtype=bool)
        self.step_cfg = np.full((b, s_max), NO_CONFIG, dtype=np.int64)
        self.step_vol = np.zeros((b, s_max))
        self.t_recfg = np.zeros(b)
        for bi, (fabric, pattern) in enumerate(cells):
            n_p, n_s = fabric.n_planes, pattern.n_steps
            bw, init = fabric_arrays(fabric)
            self.bw[bi, :n_p] = bw
            self.config[bi, :n_p] = init
            self.real[bi, :n_p] = True
            self.step_cfg[bi, :n_s] = pattern.configs
            self.step_vol[bi, :n_s] = pattern.volumes
            self.t_recfg[bi] = fabric.t_recfg
        if mode is DependencyMode.CHAIN:
            self._init_chain_tables()
            self._init_candidate_table()
        # Physically-installed configs (the executor's lazy state): only
        # direct serves advance it, never reserve retargets -- bypass
        # relay depths are derived from this, not from `config`.
        self.installed = self.config.copy()
        # Per-instance self-relay depth tables, padded to the grid's max
        # config-id range; all-zero (shape (B, 0, 0)) when bypassing is
        # off, which turns the bypass row expansion into a no-op.
        if mode is DependencyMode.CHAIN and bypass_depth >= 2:
            tabs = [
                relay_depth_table(pattern, bypass_depth)
                for _, pattern in cells
            ]
            c_max = max(t.shape[0] for t in tabs)
            self.depth_tab = np.zeros((b, c_max, c_max), dtype=np.int64)
            for bi, t in enumerate(tabs):
                self.depth_tab[bi, : t.shape[0], : t.shape[1]] = t
        else:
            self.depth_tab = np.zeros((b, 0, 0), dtype=np.int64)

    def _init_chain_tables(self) -> None:
        """Rollout tail tables + the ``prev_same`` first-occurrence table."""
        b, s_max = len(self.cells), self.s_max
        # Tail lower-bound tables (same summation order as rollout_batch:
        # a direct np.sum over the suffix slice, per start offset).
        self.bw_sum = np.array(
            [self.bw[bi, : self.n_p[bi]].sum() for bi in range(b)]
        )
        self.suffix_vol = np.zeros((b, s_max + 1))
        self.suffix_changes = np.zeros((b, s_max + 1), dtype=np.int64)
        # prev_same[bi, k]: largest k' < k with the same step config, else
        # -1 -- so "k is the first occurrence of its config in steps >= s"
        # is the O(1) test prev_same[bi, k] < s.
        self.prev_same = np.full((b, s_max), -1, dtype=np.int64)
        for bi in range(b):
            n_s = int(self.n_s[bi])
            last_seen: dict[int, int] = {}
            for k in range(n_s):
                # Per-offset direct np.sum: load-bearing for float-order
                # parity with rollout_batch's tail_volume computation.
                self.suffix_vol[bi, k] = self.step_vol[bi, k:n_s].sum()
                cfg = int(self.step_cfg[bi, k])
                self.prev_same[bi, k] = last_seen.get(cfg, -1)
                last_seen[cfg] = k
            if n_s > 1:
                # suffix_changes[k] counts adjacent config changes in
                # steps k..n_s-1; integer-exact, so a reverse cumsum is
                # bitwise-identical to the O(S^2) counting loop.
                changes = (
                    self.step_cfg[bi, 1:n_s] != self.step_cfg[bi, : n_s - 1]
                ).astype(np.int64)
                self.suffix_changes[bi, : n_s - 1] = np.cumsum(
                    changes[::-1]
                )[::-1]

    def _init_candidate_table(self) -> None:
        """Flat padded reserve-set rows, in `_reserve_candidates` order.

        Enumerated instances (``n_planes <= max_enumerated_planes``) get
        static masks: every subset except the full set, sizes ascending,
        lexicographic within a size (the ``itertools.combinations``
        order).  Larger instances get 4 *dynamic* rows -- soonest-free
        prefixes of sizes 0..3 -- whose masks are refreshed from ``free``
        at every step (`_refresh_dynamic_rows`).
        """
        b, p_max = len(self.cells), self.p_max
        masks: list[np.ndarray] = []
        inst: list[int] = []
        self.cand_start = np.zeros(b, dtype=np.int64)
        dynamic: list[int] = []
        for bi in range(b):
            n_p = int(self.n_p[bi])
            self.cand_start[bi] = len(inst)
            if n_p <= self.max_enumerated_planes:
                for size in range(n_p):
                    for combo in itertools.combinations(range(n_p), size):
                        m = np.zeros(p_max, dtype=bool)
                        m[list(combo)] = True
                        masks.append(m)
                        inst.append(bi)
            else:
                dynamic.append(bi)
                for _ in range(4):  # sizes 0..3, refreshed per step
                    masks.append(np.zeros(p_max, dtype=bool))
                    inst.append(bi)
        self.cand_mask = np.stack(masks, axis=0)
        self.cand_inst = np.asarray(inst, dtype=np.int64)
        self.cand_size = self.cand_mask.sum(axis=1)
        self.cand_valid = self.cand_size != self.n_p[self.cand_inst]
        self.dyn_insts = np.asarray(dynamic, dtype=np.int64)

    def _refresh_dynamic_rows(self, live: np.ndarray) -> None:
        """Rebuild soonest-free prefix masks for live fallback instances.

        Matches ``_reserve_candidates``'s ``sorted(range(n_planes),
        key=free)`` (stable: free-time ties break by plane index) via a
        stable argsort; prefixes longer than ``n_planes`` saturate to the
        full plane set exactly like ``set(by_free[:size])`` does.
        """
        if not self.dyn_insts.size:
            return
        dyn = self.dyn_insts[live[self.dyn_insts]]
        if not dyn.size:
            return
        ranks = _stable_ranks(
            np.where(self.real[dyn], self.free[dyn], np.inf)
        )
        for size in range(4):
            rows = self.cand_start[dyn] + size
            self.cand_mask[rows] = (ranks < size) & self.real[dyn]
        rows = (self.cand_start[dyn][:, None] + np.arange(4)).ravel()
        self.cand_size[rows] = self.cand_mask[rows].sum(axis=1)
        self.cand_valid[rows] = (
            self.cand_size[rows] != self.n_p[self.cand_inst[rows]]
        )

    def upcoming_targets_table(
        self, step_idx: int
    ) -> tuple[np.ndarray, np.ndarray]:
        """Per-instance retarget tables for reserve sets at ``step_idx``.

        Returns ``(targets (B, P_max), n_avail (B,))``: for each instance,
        the first ``P_max`` distinct configs of steps ``step_idx + 1..``
        (first-occurrence order) that are neither installed on a plane nor
        equal to the current step's config -- the array twin of
        ``_upcoming_targets`` with ``held`` = installed + current.
        """
        b, p_max = len(self.cells), self.p_max
        targets = np.full((b, p_max), NO_CONFIG, dtype=np.int64)
        s = step_idx + 1
        if s >= self.s_max:
            return targets, np.zeros(b, dtype=np.int64)
        window = self.step_cfg[:, s:]
        first_occ = self.prev_same[:, s:] < s
        in_window = np.arange(s, self.s_max)[None, :] < self.n_s[:, None]
        held = (window[:, :, None] == self.config[:, None, :]).any(axis=2)
        held |= window == self.step_cfg[:, step_idx][:, None]
        avail = first_occ & ~held & in_window
        slot = np.cumsum(avail, axis=1) - 1
        take = avail & (slot < p_max)
        bi, wi = np.nonzero(take)
        targets[bi, slot[bi, wi]] = window[bi, wi]
        return targets, avail.sum(axis=1)


def _stable_ranks(key: np.ndarray) -> np.ndarray:
    """Per-row rank of each column under a stable ascending sort of ``key``.

    Ties rank in column order -- the ``sorted(sorted(...), key=...)``
    rule of ``_reserve_candidates``.  The single source of the rank
    computation both the batched retarget pairing (`_reserve_rows`) and
    the dynamic prefix masks (`_refresh_dynamic_rows`) rely on for the
    bitwise-parity contract.
    """
    order = np.argsort(key, axis=1, kind="stable")
    ranks = np.empty_like(order)
    np.put_along_axis(
        ranks, order, np.arange(key.shape[1])[None, :], axis=1
    )
    return ranks


def _reserve_rows(
    st: _GridState, step_idx: int, live: np.ndarray
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray,
           np.ndarray]:
    """Batched candidate reserve-set states across every live instance.

    The vectorized twin of per-instance ``_reserve_candidates`` calls:
    returns ``(inst, starts, trial_cfg, trial_free, reserved_mask,
    valid)`` where rows are grouped contiguously per live instance
    (``starts`` marks each instance's first row).  Reserved planes are
    retargeted toward upcoming configs soonest-free first (stable on
    ties, matching the reference's deterministic sort), with the same
    single ``free + t_recfg`` float bump -- so downstream scores, and
    therefore selections, are bitwise identical.
    """
    st._refresh_dynamic_rows(live)
    rows = np.nonzero(live[st.cand_inst])[0]
    inst = st.cand_inst[rows]
    starts = np.nonzero(np.r_[True, inst[1:] != inst[:-1]])[0]
    mask = st.cand_mask[rows]
    free_rows = st.free[inst]
    cfg_rows = st.config[inst]
    # Rank reserved planes by (free time, plane index): stable argsort
    # over free with non-reserved planes pushed to +inf.
    ranks = _stable_ranks(np.where(mask, free_rows, np.inf))
    targets, n_avail = st.upcoming_targets_table(step_idx)
    n_tgt = np.minimum(st.cand_size[rows], n_avail[inst])
    assigned = mask & (ranks < n_tgt[:, None])
    tgt = np.take_along_axis(targets[inst], ranks, axis=1)
    trial_free = np.where(
        assigned, free_rows + st.t_recfg[inst][:, None], free_rows
    )
    trial_cfg = np.where(assigned, tgt, cfg_rows)
    return inst, starts, trial_cfg, trial_free, mask, st.cand_valid[rows]


def _rollout_rows(
    st: _GridState,
    inst: np.ndarray,  # (R,) row -> instance index
    cfg: np.ndarray,  # (R, P_max)
    free: np.ndarray,  # (R, P_max)
    barrier: np.ndarray,  # (R,)
    start_step: int,
    horizon: int,
) -> np.ndarray:
    """Row-batched twin of ``rollout_batch`` with per-row step tables.

    Rows belonging to different grid cells roll out their own remaining
    steps (masked once a row's pattern runs out); the arithmetic per row
    matches the per-instance ``rollout_batch`` operation for operation, so
    scores -- and therefore candidate selections -- are bitwise identical.
    """
    cfg = cfg.copy()
    free = free.copy()
    barrier = barrier.copy()
    bw_rows = st.bw[inst]
    real_rows = st.real[inst]
    t_rows = st.t_recfg[inst][:, None]
    end_step = np.minimum(st.n_s[inst], start_step + horizon)
    stop = int(min(st.s_max, start_step + horizon))
    for k in range(start_step, stop):
        live = k < st.n_s[inst]
        if not live.any():
            break
        cfg_k = st.step_cfg[inst, k][:, None]
        vol_k = np.where(live, st.step_vol[inst, k], 0.0)
        extra = np.where(cfg == cfg_k, 0.0, t_rows)
        ready = np.maximum(barrier[:, None], free + extra)
        ready = np.where(real_rows, ready, _BIG)
        level, split = waterfill_batch(ready, bw_rows, vol_k)
        active = (split > 0.0) & live[:, None]
        free = np.where(active, level[:, None], free)
        cfg = np.where(active, cfg_k, cfg)
        barrier = np.where(live, level, barrier)
    # Aggregate-bandwidth tail past the horizon (two separate additions,
    # matching rollout_batch's float evaluation order).
    has_tail = end_step < st.n_s[inst]
    tail_vol = st.suffix_vol[inst, end_step] / st.bw_sum[inst]
    barrier = np.where(has_tail, barrier + tail_vol, barrier)
    tail_rec = (
        st.suffix_changes[inst, end_step] * st.t_recfg[inst] / st.n_p[inst]
    )
    return np.where(has_tail, barrier + tail_rec, barrier)


def _chain_grid_chosen(
    st: _GridState, rollout_horizon: int
) -> list[tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """The batched CHAIN per-step loop: no per-instance Python inside.

    Each step costs ONE `_reserve_rows` (batched candidate construction
    from the precomputed reserve-set table), ONE ``waterfill_batch``, ONE
    row-batched rollout, and ONE instance-keyed lexsort selecting every
    live instance's winner at once.  Chosen splits land in per-step
    ``(live_insts, split, byp_h)`` tuples -- the same structure the fused
    on-device planner (`repro.core.ir.fused`) emits, so both planners
    share one Decisions materialization epilogue.
    """
    b = len(st.cells)
    with_bypass = st.bypass_depth >= 2
    chosen: list[tuple[np.ndarray, np.ndarray, np.ndarray]] = []
    for i in range(st.s_max):
        live = i < st.n_s
        if not live.any():
            break
        inst, starts, trial_cfg, trial_free, reserved_mask, valid = (
            _reserve_rows(st, i, live)
        )
        byp_h = np.zeros_like(trial_cfg)
        if with_bypass and st.depth_tab.shape[1]:
            # Bypass twin rows, appended after ALL base rows: within one
            # instance every base row still precedes every bypass row in
            # the global candidate order, which is exactly the
            # per-instance `_chain_decisions` enumeration -- so the
            # instance-keyed lexsort selects identically.
            c_max = st.depth_tab.shape[1]
            scfg = st.step_cfg[inst, i]
            inst_rows = st.installed[inst]
            known = (inst_rows >= 0) & (inst_rows < c_max)
            plane_hops = np.where(
                known,
                st.depth_tab[
                    inst[:, None],
                    np.clip(inst_rows, 0, c_max - 1),
                    np.clip(scfg, 0, c_max - 1)[:, None],
                ],
                0,
            )
            hops = np.where(
                reserved_mask | (trial_cfg == scfg[:, None]),
                0,
                plane_hops,
            )
            inst = np.concatenate([inst, inst])
            trial_cfg = np.concatenate([trial_cfg, trial_cfg], axis=0)
            trial_free = np.concatenate([trial_free, trial_free], axis=0)
            reserved_mask = np.concatenate(
                [reserved_mask, reserved_mask], axis=0
            )
            valid = np.concatenate([valid, valid & hops.any(axis=1)])
            byp_h = np.concatenate([np.zeros_like(hops), hops], axis=0)
        bypassing = byp_h >= 2
        cfg_i = st.step_cfg[inst, i][:, None]
        vol_i = st.step_vol[inst, i]
        extra = np.where(
            (trial_cfg == cfg_i) | bypassing,
            0.0,
            st.t_recfg[inst][:, None],
        )
        ready = np.maximum(st.barrier[inst][:, None], trial_free + extra)
        ready = np.where(reserved_mask | ~st.real[inst], _BIG, ready)
        bw_rows = st.bw[inst]
        bw_eff = np.where(bypassing, bw_rows / np.maximum(byp_h, 1), bw_rows)
        level, split = waterfill_batch(ready, bw_eff, vol_i)
        valid = valid & ((vol_i <= _EPS) | (split > 0.0).any(axis=1))
        feasible = np.zeros(b, dtype=bool)
        np.logical_or.at(feasible, inst, valid)
        assert feasible[live].all(), "no feasible reserve set"
        active = split > 0.0
        new_free = np.where(active, level[:, None], trial_free)
        new_cfg = np.where(active & ~bypassing, cfg_i, trial_cfg)
        scores = _rollout_rows(
            st, inst, new_cfg, new_free, level, i + 1, rollout_horizon
        )
        scores = np.where(valid, scores, np.inf)
        level_key = np.where(valid, level, np.inf)
        # Per-instance min by (score, level, candidate order): one global
        # lexsort with the instance id as primary key; the first row of
        # each instance segment is exactly its per-slice lexsort()[0].
        order = np.lexsort(
            (np.arange(inst.shape[0]), level_key, scores, inst)
        )
        inst_sorted = inst[order]
        seg = np.nonzero(
            np.r_[True, inst_sorted[1:] != inst_sorted[:-1]]
        )[0]
        best = order[seg]
        live_insts = inst_sorted[seg]
        st.config[live_insts] = new_cfg[best]
        st.free[live_insts] = new_free[best]
        st.barrier[live_insts] = level[best]
        # Installed state mirrors the executor's idle-split filter, like
        # the per-instance loop.
        st.installed[live_insts] = np.where(
            (split[best] > _EPS_VOLUME) & ~bypassing[best],
            st.step_cfg[live_insts, i][:, None],
            st.installed[live_insts],
        )
        chosen.append((live_insts, split[best], byp_h[best]))
    return chosen


def _chain_grid_decisions(
    st: _GridState, rollout_horizon: int, planner: str = "step"
) -> list[Decisions]:
    """Materialize CHAIN-mode grid Decisions from either planner.

    ``planner="step"`` runs the per-step numpy loop
    (`_chain_grid_chosen`); ``"fused"`` runs the whole loop as one jitted
    ``lax.scan`` on device (`repro.core.ir.fused`) -- bitwise-identical
    chosen splits by contract (property-tested), so the materialization
    below is shared verbatim.
    """
    b = len(st.cells)
    with_bypass = st.bypass_depth >= 2
    if planner == "fused":
        from repro.core.ir.fused import fused_chain_grid_chosen

        chosen = fused_chain_grid_chosen(st, rollout_horizon)
    else:
        chosen = _chain_grid_chosen(st, rollout_horizon)

    splits: list[list[dict[int, float]]] = [[] for _ in range(b)]
    bypass_steps: list[list[tuple[BypassRoute, ...]]] = [
        [] for _ in range(b)
    ]
    for live_insts, split, byph in chosen:
        for row, bi in enumerate(live_insts):
            n_p = int(st.n_p[bi])
            splits[bi].append(
                {
                    j: float(split[row, j])
                    for j in range(n_p)
                    if split[row, j] > 0.0 and byph[row, j] < 2
                }
            )
            bypass_steps[bi].append(
                tuple(
                    BypassRoute(
                        planes=(j,) * int(byph[row, j]),
                        volume=float(split[row, j]),
                    )
                    for j in range(n_p)
                    if split[row, j] > 0.0 and byph[row, j] >= 2
                )
            )
    return [
        Decisions(
            tuple(s),
            bypass=tuple(bp) if with_bypass else None,
        )
        for s, bp in zip(splits, bypass_steps)
    ]


def _independent_grid_decisions(
    st: _GridState, planner: str = "step"
) -> list[Decisions]:
    """Batched INDEPENDENT-mode step packing (least-finish-time).

    The instance-batched twin of ``independent_decisions``: every live
    instance's argmin-packing decision for step ``i`` comes from one
    (batch, planes) finish-time computation.  Padded/dead rows are masked
    to +inf, so per-instance argmins -- and the resulting splits -- are
    bitwise identical to the per-instance loop.  ``planner="fused"``
    replaces the loop with the one-program device scan
    (`repro.core.ir.fused`), same chosen tuples by contract.
    """
    b = len(st.cells)
    chosen: list[tuple[np.ndarray, np.ndarray, np.ndarray]] = []
    if planner == "fused":
        from repro.core.ir.fused import fused_independent_grid_chosen

        chosen = fused_independent_grid_chosen(st)
    else:
        for i in range(st.s_max):
            live = i < st.n_s
            if not live.any():
                break
            cfg_i = st.step_cfg[:, i][:, None]
            extra = np.where(
                st.config == cfg_i, 0.0, st.t_recfg[:, None]
            )
            finish = st.free + extra + st.step_vol[:, i][:, None] / st.bw
            finish = np.where(st.real, finish, np.inf)
            j = np.argmin(finish, axis=1)
            rows = np.nonzero(live)[0]
            jl = j[rows]
            st.free[rows, jl] = finish[rows, jl]
            st.config[rows, jl] = st.step_cfg[rows, i]
            chosen.append((rows, jl, st.step_vol[rows, i]))
    splits: list[list[dict[int, float]]] = [[] for _ in range(b)]
    for rows, jl, vols in chosen:
        for bi, j, v in zip(rows, jl, vols):
            splits[bi].append({int(j): float(v)})
    return [
        Decisions(tuple(s), mode=DependencyMode.INDEPENDENT)
        for s in splits
    ]


def _independent_split_grid_decisions(
    st: _GridState, planner: str = "step"
) -> list[Decisions]:
    """Batched INDEPENDENT-mode water-fill splitting.

    The instance-batched twin of ``independent_split_decisions``: every
    live instance's step splits across its planes in ONE
    ``waterfill_batch`` call with per-row volumes -- the
    plane-heterogeneous path (straggler planes absorb proportionally
    less), where argmin packing would stall whole steps on slow planes.
    Padded planes are masked to ``_BIG`` ready times, so per-instance
    levels and splits are bitwise identical to the per-instance loop.
    ``planner="fused"`` runs the same recurrence as one device scan
    (`repro.core.ir.fused`), same chosen tuples by contract.
    """
    b = len(st.cells)
    chosen: list[tuple[np.ndarray, np.ndarray]] = []
    if planner == "fused":
        from repro.core.ir.fused import (
            fused_independent_split_grid_chosen,
        )

        chosen = fused_independent_split_grid_chosen(st)
    else:
        for i in range(st.s_max):
            live = i < st.n_s
            if not live.any():
                break
            cfg_i = st.step_cfg[:, i][:, None]
            extra = np.where(
                st.config == cfg_i, 0.0, st.t_recfg[:, None]
            )
            ready = np.where(st.real, st.free + extra, _BIG)
            vol_i = np.where(live, st.step_vol[:, i], 0.0)
            level, split = waterfill_batch(ready, st.bw, vol_i)
            active = (split > 0.0) & live[:, None]
            st.free = np.where(active, level[:, None], st.free)
            st.config = np.where(active, cfg_i, st.config)
            chosen.append((np.nonzero(live)[0], split))
    splits: list[list[dict[int, float]]] = [[] for _ in range(b)]
    for rows, split in chosen:
        for bi in rows:
            splits[bi].append(
                {
                    j: float(split[bi, j])
                    for j in range(int(st.n_p[bi]))
                    if split[bi, j] > 0.0
                }
            )
    return [
        Decisions(tuple(s), mode=DependencyMode.INDEPENDENT)
        for s in splits
    ]


def swot_greedy_grid(
    cells: Sequence[tuple[OpticalFabric, Pattern]],
    rollout_horizon: int = 24,
    max_enumerated_planes: int = 8,
    backend: "str | TimingBackend | None" = None,
    mode: DependencyMode = DependencyMode.CHAIN,
    bypass_depth: int = 0,
    independent_split: bool = False,
    planner: str | None = None,
    attribution: bool = False,
) -> list[GridPlan]:
    """Plan a whole grid of (fabric, pattern) cells in one batched pass.

    The instance-batched greedy: every cell advances through the per-step
    loop together.  CHAIN mode scores each step's candidate reserve sets
    across ALL cells with one ``waterfill_batch`` + one row-batched
    rollout call, drawing candidates from a reserve-set table precomputed
    at grid construction; INDEPENDENT mode packs every cell's step by
    least finish time in one batched argmin -- or, with
    ``independent_split=True``, water-fills every cell's step across its
    planes in one per-row-volume ``waterfill_batch`` call (the
    plane-heterogeneous path).  Per-cell decisions are bitwise identical
    to ``swot_greedy_chain(..., polish=False)`` /
    ``independent_decisions`` / ``independent_split_decisions``
    respectively (property-tested); the final CCT/utilization scoring
    runs through ``batch_evaluate`` on the chosen IR backend.

    ``backend=None`` auto-selects jax once the grid reaches
    ``REPRO_GRID_BACKEND_THRESHOLD`` cells (default
    ``DEFAULT_GRID_BACKEND_THRESHOLD``; the arbiter's shared
    `select_backend_by_size` policy), else follows the
    ``REPRO_IR_BACKEND``/numpy default; an explicit ``backend`` always
    wins.

    ``bypass_depth >= 2`` (CHAIN mode) plans a Topology-Bypassing twin
    grid and keeps, per cell, whichever decisions score the strictly
    better CCT on the deterministic numpy backend -- the same guarded
    pick as ``swot_greedy_chain``, so per-cell parity holds with
    ``swot_greedy_chain(polish=False, bypass_depth=...)``.

    ``planner`` picks how the per-step loop executes: ``"step"`` (the
    numpy loop, one batched dispatch per step), ``"fused"`` (the whole
    loop as ONE jitted ``lax.scan`` device program,
    `repro.core.ir.fused` -- bitwise-identical decisions by contract),
    or ``None`` to auto-select fused once the grid reaches
    ``REPRO_FUSED_PLANNER_THRESHOLD`` cells
    (`select_planner_by_size`).

    ``attribution=True`` threads the CCT decomposition through the final
    scoring pass: each returned ``GridPlan.attribution`` carries its
    cell's (S, P) `repro.obs.attribution.Attribution` slice.  Composes
    with every planner/backend combination (the fused planner's
    decisions are bitwise-equal, and all timing backends emit the
    component cubes).

    LP polish is deliberately per-instance-only (it solves one LP per
    cell), so the grid path trades it away for throughput; sweeps that
    need polished cells can re-run the winners through ``swot_greedy``.
    """
    if not cells:
        return []
    if independent_split and mode is DependencyMode.CHAIN:
        raise ValueError(
            "independent_split=True requires mode=INDEPENDENT"
        )
    backend = select_backend_by_size(
        len(cells),
        ENV_GRID_BACKEND_THRESHOLD,
        DEFAULT_GRID_BACKEND_THRESHOLD,
        explicit=backend,
    )
    planner = select_planner_by_size(len(cells), explicit=planner)
    st = _GridState(cells, mode=mode,
                    max_enumerated_planes=max_enumerated_planes)
    if mode is DependencyMode.CHAIN:
        decisions = _chain_grid_decisions(st, rollout_horizon, planner)
        st_byp = (
            _GridState(
                cells, mode=mode,
                max_enumerated_planes=max_enumerated_planes,
                bypass_depth=bypass_depth,
            )
            if bypass_depth >= 2
            else None
        )
        # Mirror the per-instance `depth_tab.any()` guard: a grid with
        # no self-relay opportunity anywhere (e.g. all xor pairings)
        # skips the twin pass and its two scoring passes entirely.
        if st_byp is not None and st_byp.depth_tab.any():
            byp_decisions = _chain_grid_decisions(
                st_byp, rollout_horizon, planner
            )
            base_cct = batch_evaluate(
                [
                    BatchInstance(fabric, pattern, dec)
                    for (fabric, pattern), dec in zip(cells, decisions)
                ],
                backend="numpy",
            ).cct
            byp_cct = batch_evaluate(
                [
                    BatchInstance(fabric, pattern, dec)
                    for (fabric, pattern), dec in zip(cells, byp_decisions)
                ],
                backend="numpy",
            ).cct
            decisions = [
                byp
                if (
                    byp.bypass is not None
                    and any(byp.bypass)
                    and byp_cct[bi] < base_cct[bi]
                )
                else base
                for bi, (base, byp) in enumerate(
                    zip(decisions, byp_decisions)
                )
            ]
    elif independent_split:
        decisions = _independent_split_grid_decisions(st, planner)
    else:
        decisions = _independent_grid_decisions(st, planner)
    result = batch_evaluate(
        [
            BatchInstance(fabric, pattern, dec)
            for (fabric, pattern), dec in zip(st.cells, decisions)
        ],
        backend=backend,
        attribution=attribution,
    )
    return [
        GridPlan(
            fabric=fabric,
            pattern=pattern,
            decisions=dec,
            cct=float(result.cct[bi]),
            n_reconfigurations=int(result.n_reconfigurations[bi]),
            utilization=float(result.utilization[bi]),
            attribution=(
                _slice_attribution(result.attribution, bi)
                if attribution
                else None
            ),
        )
        for bi, ((fabric, pattern), dec) in enumerate(
            zip(st.cells, decisions)
        )
    ]


def _slice_attribution(att, bi: int):
    """One cell's (S, P) Attribution view from the batch decomposition."""
    import dataclasses as _dc

    return _dc.replace(
        att,
        t_xmit=att.t_xmit[bi],
        t_bypass=att.t_bypass[bi],
        t_recfg_wait=att.t_recfg_wait[bi],
        t_recfg_hidden=att.t_recfg_hidden[bi],
        t_idle=att.t_idle[bi],
        cct=att.cct[bi],
        step_mask=att.step_mask[bi],
        plane_mask=att.plane_mask[bi],
    )
