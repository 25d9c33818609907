"""Fabric arbiter: plane leases for concurrent collectives.

The serial path (``OpticalController.trigger``) models one collective at a
time owning every OCS plane.  The arbiter makes the fabric a shared
resource with an event-driven execution model:

* **Admission** -- ``submit`` enqueues a ``CollectiveRequest``; a job is
  admitted when at least ``min_planes`` planes are free.  The admission
  queue is priority-ordered (higher ``priority`` first, FIFO within a
  priority); an optional ``max_queue_depth`` applies backpressure by
  rejecting submissions once the queue is full.
* **Leases** -- an admitted job receives an exclusive lease on a subset
  of planes (all free planes when nothing else is waiting, otherwise its
  fair share).  No plane is ever owned by two in-flight collectives;
  ``assert_invariants`` checks this partition property.  The
  ``placement`` policy picks *which* free planes: ``"first_free"``
  (lowest ids, the historical rule) or ``"schedule_aware"`` (prefer
  planes whose installed circuits already match the job's next-step
  config in its namespace, so co-located same-``ConfigKey`` tenants skip
  reconfigurations entirely).
* **Planning** -- the job's remaining steps are scheduled on a
  *sub-fabric* (its leased planes only) by the existing SWOT scheduler,
  so every single-collective optimization (reconfiguration-communication
  overlap, water-filling splits, LP polish) applies unchanged.  With a
  full-fabric lease this degenerates to exactly the serial plan.
* **Re-planning** -- lease changes take effect at step boundaries (a
  plane cannot be revoked mid-transmission): a job asked to shrink
  releases planes and re-plans its remaining steps on the smaller
  sub-fabric; freed planes are granted to waiting jobs or offered to
  running ones (grow), which likewise absorb them at their next boundary.
  Re-plans pass per-plane *ready offsets* into the scheduler, so the
  sub-schedule starts on the earliest-freeing plane instead of stalling
  to the latest one, and shrink decisions re-score candidate kept-sets
  with one batched IR evaluation (``repro.core.ir.batch_evaluate``).
  INDEPENDENT-mode jobs have no step barrier, so they resize only at
  completion.

**The memoized hot path** (``optimize=True``, the default; DESIGN.md
section 18): planning results are cached in a ``PlanCache`` keyed on
everything the plan depends on -- (algorithm, n_nodes, size, remaining
step, method, mode, lease width, per-plane bandwidth scales, namespaced
installed configs, per-plane ready offsets) -- and stored in
plan-*relative* time, so a same-key job re-uses the cached schedule
time-shifted to its own grant instant.  All grants pending at one
timestamp are planned through ONE instance-batched greedy pass
(``swot_greedy_chain_batch``) instead of per-job ``swot_schedule``
calls, lease-shrink scoring due at a shared boundary collapses into one
``batch_evaluate`` across jobs, and completed plans retire in O(planes)
from a per-plan summary instead of re-walking activities.  Every reuse
replays the exact float operations of the uncached path, so replay
reports are bit-identical with ``optimize`` on or off (property-tested).

Physical OCS state is tracked across jobs: a plane's installed
permutation is tagged by ``(algorithm, n_nodes)`` -- the namespace within
which config ids denote identical port maps -- so a follow-up job running
the *same* algorithm at the same communicator size reuses installed
circuits, while any other job pays the reconfiguration.
"""

from __future__ import annotations

import dataclasses
import heapq
import itertools
import time

from repro.core.baselines import strawman_instance
from repro.core.fabric import OpticalFabric
from repro.core.greedy import swot_greedy_chain_batch
from repro.core.ir import (
    BatchInstance,
    batch_evaluate,
)
from repro.core import knobs
from repro.core.ir.backends import select_backend_by_size
from repro.core.patterns import Pattern, get_pattern
from repro.core.schedule import DependencyMode, Kind, Schedule
from repro.core.scheduler import swot_schedule
from repro.core.shim import _INDEPENDENT_SAFE, CollectiveRequest
from repro.obs.trace import NULL_TRACER, Tracer
from repro.runtime.engine import SimEngine
from repro.runtime.plancache import CachedPlan, PlanCache
from repro.core.tolerances import EPS as _EPS

# Cap on lease-shrink candidate sets scored per resize (one batched IR
# evaluation covers all of them).
_MAX_RELEASE_CANDIDATES = 16

# Candidate-batch size at and above which the arbiter auto-selects the
# jax IR backend for lease re-scoring (numpy below it -- small batches
# cannot amortize jit dispatch).  The default equals the candidate cap,
# so exactly the maximum-size shrink batches -- the only ones where the
# batched recurrence dominates the evaluation -- flip to jax; it must
# stay <= _MAX_RELEASE_CANDIDATES or auto-selection becomes unreachable.
# Override with the env var; <= 0 disables auto-selection entirely.
# Name and default live in `repro.core.knobs` (single read point).
ENV_BACKEND_THRESHOLD = knobs.ENV_ARBITER_BACKEND_THRESHOLD
_DEFAULT_BACKEND_THRESHOLD = knobs.DEFAULT_ARBITER_BACKEND_THRESHOLD
assert _DEFAULT_BACKEND_THRESHOLD <= _MAX_RELEASE_CANDIDATES, (
    "auto-selection unreachable: knobs.DEFAULT_ARBITER_BACKEND_THRESHOLD "
    "must stay <= _MAX_RELEASE_CANDIDATES"
)

# Lease placement policies (see class docstring).
_PLACEMENTS = ("first_free", "schedule_aware")

# Namespace within which OCS config ids denote identical permutations.
ConfigKey = tuple[str, int]  # (algorithm, n_nodes)


@dataclasses.dataclass
class JobRecord:
    """Per-job outcome statistics."""

    job_id: int
    tag: str
    algorithm: str
    n_nodes: int
    size: float
    priority: int
    arrival: float
    start: float | None = None  # admission (lease grant) time
    finish: float | None = None
    replans: int = 0
    planes_min: int = 0
    planes_max: int = 0
    rejected: bool = False
    # Which workload the job belongs to (the JobSpec.tenant label);
    # purely descriptive -- admission and leasing never read it.
    tenant: str = ""
    # Collective call-site label (threaded from TraceEvent.site_id via
    # trace_to_jobs); empty for ad-hoc submissions -- metric rollups
    # then fall back to ``tag``.
    site_id: str = ""
    # Live CCT attribution, accumulated as plan segments retire: each
    # component is the *plane-mean* seconds over the job's lease (per
    # segment), so once the job completes
    # ``t_xmit + t_bypass + t_recfg_exposed + t_recfg_hidden + t_idle``
    # equals ``cct`` bitwise -- ``t_idle`` is set at completion as the
    # exact closing complement (it can dip below zero only when an
    # in-flight reconfiguration runs past a resize boundary).
    t_xmit: float = 0.0
    t_bypass: float = 0.0
    t_recfg_exposed: float = 0.0
    t_recfg_hidden: float = 0.0
    t_idle: float = 0.0

    @property
    def queueing_delay(self) -> float | None:
        return None if self.start is None else self.start - self.arrival

    @property
    def cct(self) -> float | None:
        if self.finish is None or self.start is None:
            return None
        return self.finish - self.start

    @property
    def response_time(self) -> float | None:
        return None if self.finish is None else self.finish - self.arrival

    @property
    def site(self) -> str:
        """Attribution-rollup label: ``site_id`` when threaded, else
        the submission tag."""
        return self.site_id or self.tag

    @property
    def overlap_efficiency(self) -> float | None:
        """Hidden / (hidden + exposed) reconfiguration time for this
        job; 1.0 when it carried none (vacuous), None until finished."""
        if self.finish is None:
            return None
        total = self.t_recfg_hidden + self.t_recfg_exposed
        return self.t_recfg_hidden / total if total > 0.0 else 1.0


@dataclasses.dataclass
class ArbiterStats:
    """Aggregate fabric statistics."""

    admitted: int = 0
    completed: int = 0
    rejected: int = 0
    replans: int = 0
    reconfigurations: int = 0
    plane_busy: dict[int, float] = dataclasses.field(default_factory=dict)

    def utilization(self, makespan: float, n_planes: int) -> float:
        """Mean fraction of [0, makespan] planes spent transmitting or
        reconfiguring."""
        if makespan <= 0:
            return 0.0
        busy = sum(self.plane_busy.get(j, 0.0) for j in range(n_planes))
        return busy / (makespan * n_planes)


@dataclasses.dataclass
class _Job:
    job_id: int
    req: CollectiveRequest
    pattern: Pattern
    priority: int
    mode: DependencyMode
    record: JobRecord
    method: str = "greedy"
    planes: tuple[int, ...] = ()
    step_idx: int = 0
    plan: Schedule | None = None
    cached: CachedPlan | None = None
    plan_base_step: int = 0
    plan_t0: float = 0.0
    boundaries: tuple[float, ...] = ()
    target_planes: int = 0
    pending_planes: tuple[int, ...] = ()
    planned: bool = False
    lease_since: float = 0.0  # last grant/resize instant (metrics only)

    @property
    def key(self) -> ConfigKey:
        return (self.req.algorithm, self.req.n_nodes)


def _rel_bounds(
    mode: DependencyMode, schedule: Schedule, n_steps: int
) -> tuple[float, ...]:
    """Plan-relative step-boundary offsets for a freshly built schedule.

    The arbiter materializes absolute boundaries as ``t0 + rel`` -- the
    same float additions whether the plan is fresh or replayed from the
    cache, which is what keeps memoization bit-invisible.
    """
    if mode is DependencyMode.INDEPENDENT:
        # No cross-step barrier: the collective is one atomic segment.
        return (schedule.cct,)
    ends: list[float] = []
    prev = 0.0
    for i in range(n_steps):
        try:
            _, end = schedule.step_window(i)
            prev = end
        except ValueError:
            pass  # zero-volume step: shares the previous boundary
        ends.append(prev)
    return tuple(ends)


def _release_candidates(
    prof: tuple, n_release: int
) -> list[tuple[int, ...]]:
    """Candidate release sets as *positions* into the sorted lease.

    The historical soonest-free choice first, then up to
    ``_MAX_RELEASE_CANDIDATES`` alternatives enumerated in free-time
    order (ties by position) so the capped pool spans soonest- through
    latest-freeing release sets.  Positions (not plane ids) make the
    enumeration a pure function of the lease *profile*, which is what
    lets physically different but profile-identical leases share one
    memoized choice.  Profile free offsets are *unclamped* (they may be
    negative for long-idle reserved planes), so this ordering equals the
    legacy (absolute free time, plane id) ordering exactly.
    """
    by_free = sorted(range(len(prof)), key=lambda i: (prof[i][0], i))
    default = tuple(by_free[:n_release])
    candidates = [default]
    seen = {frozenset(default)}
    for combo in itertools.combinations(by_free, n_release):
        if len(candidates) >= _MAX_RELEASE_CANDIDATES:
            break
        key = frozenset(combo)
        if key in seen:
            continue
        seen.add(key)
        candidates.append(combo)
    return candidates


def _pick_best(
    candidates: list[tuple[int, ...]],
    starts: list[float],
    cct,
    feasible,
    offset: int,
) -> int:
    """Earliest-estimated-finish candidate (ties keep the first choice).

    ``cct``/``feasible`` may be slices of a larger combined batch
    (``offset`` locates this job's rows); the selection arithmetic is
    identical either way.
    """
    best_idx = 0
    best_score = (
        starts[0] + float(cct[offset])
        if bool(feasible[offset])
        else float("inf")
    )
    for c in range(1, len(candidates)):
        if not bool(feasible[offset + c]):
            continue
        score = starts[c] + float(cct[offset + c])
        if score < best_score - _EPS:
            best_idx, best_score = c, score
    return best_idx


class FabricArbiter:
    """Admits concurrent collectives and leases OCS planes to them."""

    def __init__(
        self,
        engine: SimEngine,
        fabric: OpticalFabric,
        *,
        min_planes: int = 1,
        max_queue_depth: int | None = None,
        method: str = "greedy",
        allow_independent: bool = False,
        rebalance: bool = True,
        backend: str | None = None,
        tracer: Tracer | None = None,
        optimize: bool = True,
        plan_cache: PlanCache | None = None,
        placement: str = "first_free",
        metrics=None,
        record_sink=None,
        keep_records: bool = True,
    ) -> None:
        if min_planes < 1 or min_planes > fabric.n_planes:
            raise ValueError(
                f"min_planes must be in [1, {fabric.n_planes}], "
                f"got {min_planes}"
            )
        if placement not in _PLACEMENTS:
            raise ValueError(
                f"placement must be one of {_PLACEMENTS}, got {placement!r}"
            )
        self.engine = engine
        self.fabric = fabric
        self.min_planes = min_planes
        self.max_queue_depth = max_queue_depth
        self.method = method
        self.allow_independent = allow_independent
        self.rebalance = rebalance
        self.placement = placement
        # IR backend for batched lease-shrink re-scoring.  None enables
        # auto-selection: jax once the candidate batch reaches
        # REPRO_ARBITER_BACKEND_THRESHOLD rows, the REPRO_IR_BACKEND env
        # default (numpy) below it (see `_select_backend`).
        self.backend = backend
        # Structured tracing (repro.obs.trace).  The default NULL_TRACER
        # has enabled=False; every site below guards on that flag, so the
        # untraced cost is one attribute load per lifecycle event.
        self.tracer = NULL_TRACER if tracer is None else tracer
        # Live metrics (repro.obs.metrics), same NULL-default discipline
        # as the tracer: ``self._m_on`` is hoisted once and every update
        # site guards on it.  ``record_sink`` receives each JobRecord in
        # its final state (completion or rejection); ``keep_records=False``
        # drops the accumulated ``records`` dict so streaming replays
        # stay memory-flat (stats then come from the registry/sink).
        from repro.obs.metrics import NULL_REGISTRY

        self.metrics = NULL_REGISTRY if metrics is None else metrics
        self.record_sink = record_sink
        self.keep_records = keep_records
        self._m_on = self.metrics.enabled
        self._init_instruments()
        # Memoized hot path: plan + release-choice cache (DESIGN.md
        # section 18).  ``optimize=False`` disables every cached/batched
        # path and restores the per-job legacy behavior -- the reference
        # the bit-identical replay-parity tests compare against.  A
        # caller-provided ``plan_cache`` is shared (bind evicts it if it
        # served an incompatible fabric).
        self._cache: PlanCache | None = None
        if optimize:
            self._cache = plan_cache if plan_cache is not None else (
                PlanCache()
            )
            self._cache.bind(fabric)
        elif plan_cache is not None:
            raise ValueError("plan_cache requires optimize=True")
        self.stats = ArbiterStats()
        self.records: dict[int, JobRecord] = {}
        self._free: set[int] = set(range(fabric.n_planes))
        # Physical OCS state: (config-namespace key, config id) per plane.
        self._plane_state: dict[int, tuple[ConfigKey, int] | None] = {
            j: None for j in range(fabric.n_planes)
        }
        self._plane_free_at: dict[int, float] = {
            j: 0.0 for j in range(fabric.n_planes)
        }
        self._running: dict[int, _Job] = {}
        self._waiting: list[tuple[int, int, _Job]] = []  # (-prio, seq, job)
        self._ids = itertools.count()
        self._wait_seq = itertools.count()

    @property
    def plan_cache(self) -> PlanCache | None:
        """The active plan cache (None when ``optimize=False``)."""
        return self._cache

    def _trace_gauges(self) -> None:
        """Sample the fabric-level counter tracks (queue/free/running)."""
        now = self.engine.now
        self.tracer.counter("queue_depth", now, len(self._waiting))
        self.tracer.counter("free_planes", now, len(self._free))
        self.tracer.counter("running_jobs", now, len(self._running))

    def _init_instruments(self) -> None:
        """Declare every live instrument against ``self.metrics``.

        Against the NULL registry each call returns the shared no-op
        instrument, so a disabled arbiter allocates nothing.
        """
        m = self.metrics
        self._m_queue_wait = m.histogram(
            "fabric_queue_wait_seconds",
            "Admission queueing delay (arrival -> lease grant)",
            ("tenant",),
        )
        self._m_lease_s = m.histogram(
            "fabric_lease_seconds",
            "Lease segment lifetime (grant/resize -> resize/completion)",
            ("tenant",),
        )
        self._m_lease_planes = m.histogram(
            "fabric_lease_planes", "Lease width at grant and resize"
        )
        self._m_cct = m.histogram(
            "fabric_cct_seconds",
            "Collective completion time (grant -> finish)",
            ("tenant",),
        )
        self._m_jobs = m.counter(
            "fabric_jobs_total", "Jobs submitted", ("tenant",)
        )
        self._m_completed = m.counter(
            "fabric_jobs_completed_total", "Jobs completed", ("tenant",)
        )
        self._m_rejected = m.counter(
            "fabric_jobs_rejected_total",
            "Jobs rejected by backpressure",
            ("tenant",),
        )
        self._m_bytes = m.counter(
            "fabric_bytes_total", "Payload bytes completed", ("tenant",)
        )
        self._m_backpressure = m.counter(
            "fabric_backpressure_total", "Backpressure rejections"
        )
        self._m_replans = m.counter(
            "fabric_replans_total", "Lease-change re-plans"
        )
        self._mg_queue = m.gauge(
            "fabric_queue_depth", "Jobs waiting for admission"
        )
        self._mg_free = m.gauge("fabric_free_planes", "Unleased planes")
        self._mg_running = m.gauge(
            "fabric_running_jobs", "Jobs holding a lease"
        )
        # Plan-cache counters, synced by delta from the bound cache's
        # CacheStats at gauge-sample time (never inline per lookup).  A
        # cache shared across arbiters reports fleet-wide totals.
        self._m_cache_hits = m.counter(
            "fabric_plan_cache_hits_total", "Plan-cache hits"
        )
        self._m_cache_misses = m.counter(
            "fabric_plan_cache_misses_total", "Plan-cache misses"
        )
        self._m_plan_wall = m.counter(
            "fabric_plan_wall_seconds_total",
            "Wall time spent planning cache misses",
        )
        self._seen_hits = 0
        self._seen_misses = 0
        self._seen_wall = 0.0
        self._seen_replans = 0
        # Per-collective-site attribution rollups, fed at completion
        # from the job's accumulated plane-mean components.
        site_labels = ("tenant", "site")
        self._m_site_jobs = m.counter(
            "fabric_site_jobs_total",
            "Jobs completed per collective site",
            site_labels,
        )
        self._m_site_cct = m.counter(
            "fabric_site_cct_seconds_total",
            "CCT seconds per collective site",
            site_labels,
        )
        self._m_site_xmit = m.counter(
            "fabric_site_xmit_seconds_total",
            "Plane-mean direct transmission seconds per site",
            site_labels,
        )
        self._m_site_bypass = m.counter(
            "fabric_site_bypass_seconds_total",
            "Plane-mean relay-carry seconds per site",
            site_labels,
        )
        self._m_site_exposed = m.counter(
            "fabric_site_recfg_exposed_seconds_total",
            "Plane-mean exposed reconfiguration seconds per site",
            site_labels,
        )
        self._m_site_hidden = m.counter(
            "fabric_site_recfg_hidden_seconds_total",
            "Plane-mean overlapped reconfiguration seconds per site",
            site_labels,
        )
        self._m_site_idle = m.counter(
            "fabric_site_idle_seconds_total",
            "Plane-mean closing idle seconds per site",
            site_labels,
        )

    def _metric_gauges(self) -> None:
        """Publish fabric levels + plan-cache counter deltas."""
        self._mg_queue.set(len(self._waiting))
        self._mg_free.set(len(self._free))
        self._mg_running.set(len(self._running))
        if self.stats.replans != self._seen_replans:
            self._m_replans.inc(self.stats.replans - self._seen_replans)
            self._seen_replans = self.stats.replans
        if self._cache is not None:
            st = self._cache.stats
            if st.hits != self._seen_hits:
                self._m_cache_hits.inc(st.hits - self._seen_hits)
                self._seen_hits = st.hits
            if st.misses != self._seen_misses:
                self._m_cache_misses.inc(st.misses - self._seen_misses)
                self._seen_misses = st.misses
            if st.plan_wall_s != self._seen_wall:
                self._m_plan_wall.inc(st.plan_wall_s - self._seen_wall)
                self._seen_wall = st.plan_wall_s

    # -- physical prestaging ------------------------------------------------
    def prestage(self, req: CollectiveRequest) -> None:
        """Install ``req``'s first-step config on every plane (Fig. 5 setup).

        Mirrors ``OpticalFabric.prestaged`` for the serial path: the first
        admitted job of the same (algorithm, communicator) starts with hot
        circuits instead of paying a cold reconfiguration per plane.
        """
        pattern = get_pattern(req.algorithm, req.n_nodes, req.size)
        key: ConfigKey = (req.algorithm, req.n_nodes)
        for j in range(self.fabric.n_planes):
            self._plane_state[j] = (key, pattern.steps[0].config)

    # -- admission ----------------------------------------------------------
    def submit(
        self,
        req: CollectiveRequest,
        priority: int = 0,
        method: str | None = None,
        allow_independent: bool | None = None,
        *,
        tenant: str = "",
        site_id: str = "",
    ) -> JobRecord:
        """Submit one collective; returns its (live) ``JobRecord``.

        The record's ``rejected`` flag is set when backpressure drops the
        job; otherwise the job is admitted now or queued.  ``method`` /
        ``allow_independent`` override the arbiter defaults per job (the
        shim passes its own planning preferences through).  ``tenant`` /
        ``site_id`` label the record for metric rollups; neither affects
        admission or leasing.
        """
        job_id = next(self._ids)
        independent_ok = (
            self.allow_independent
            if allow_independent is None
            else allow_independent
        )
        mode = (
            DependencyMode.INDEPENDENT
            if independent_ok and req.algorithm in _INDEPENDENT_SAFE
            else DependencyMode.CHAIN
        )
        record = JobRecord(
            job_id=job_id,
            tag=req.tag or req.algorithm,
            algorithm=req.algorithm,
            n_nodes=req.n_nodes,
            size=req.size,
            priority=priority,
            arrival=self.engine.now,
            tenant=tenant,
            site_id=site_id,
        )
        if self.keep_records:
            self.records[job_id] = record
        if self._m_on:
            self._m_jobs.labels(tenant).inc()
        job = _Job(
            job_id=job_id,
            req=req,
            pattern=get_pattern(req.algorithm, req.n_nodes, req.size),
            priority=priority,
            mode=mode,
            record=record,
            method=method or self.method,
        )
        if self.tracer.enabled:
            self.tracer.instant(
                "job_arrival",
                self.engine.now,
                job=job_id,
                tag=record.tag,
                algorithm=req.algorithm,
                n_nodes=req.n_nodes,
                size=req.size,
                priority=priority,
            )
        if (
            self.max_queue_depth is not None
            and len(self._waiting) >= self.max_queue_depth
        ):
            record.rejected = True
            self.stats.rejected += 1
            if self.tracer.enabled:
                self.tracer.instant(
                    "backpressure_reject",
                    self.engine.now,
                    job=job_id,
                    queue_depth=len(self._waiting),
                )
                self._trace_gauges()
            if self._m_on:
                self._m_backpressure.inc()
                self._m_rejected.labels(tenant).inc()
                self._metric_gauges()
            if self.record_sink is not None:
                self.record_sink(record)
            return record
        heapq.heappush(
            self._waiting, (-priority, next(self._wait_seq), job)
        )
        # _drain_queue admits the job now or, if the fabric is full,
        # requests shrinks from over-share running jobs.
        self._drain_queue()
        if self.tracer.enabled:
            self._trace_gauges()
        if self._m_on:
            self._metric_gauges()
        return record

    def run_collective(
        self,
        req: CollectiveRequest,
        priority: int = 0,
        method: str | None = None,
        allow_independent: bool | None = None,
    ) -> JobRecord:
        """Submit ``req`` and run the engine until it completes (or is
        rejected).  The synchronous entry point used by the shim."""
        record = self.submit(
            req,
            priority=priority,
            method=method,
            allow_independent=allow_independent,
        )
        if record.rejected:
            return record
        while record.finish is None and self.engine.step():
            pass
        if record.finish is None:
            raise RuntimeError(
                f"job {record.job_id} never completed (deadlocked queue?)"
            )
        return record

    # -- fair-share policy --------------------------------------------------
    def _fair_share(self, extra_claimants: int = 0) -> int:
        n_claimants = (
            len(self._running) + len(self._waiting) + extra_claimants
        )
        if n_claimants == 0:
            return self.fabric.n_planes
        return max(self.min_planes, self.fabric.n_planes // n_claimants)

    def _drain_queue(self) -> None:
        # Optimized path: grants made in this drain are collected and
        # planned together (`_plan_granted`), so same-timestamp admissions
        # share one batched planning pass.  Deferral is order-preserving:
        # `_grant` schedules no events, so boundary events still land in
        # grant order (the engine's same-time tie-break).
        granted: list[_Job] | None = (
            [] if self._cache is not None else None
        )
        while self._waiting and len(self._free) >= self.min_planes:
            _, _, job = heapq.heappop(self._waiting)
            # All free planes when nothing else waits; fair share otherwise
            # (+1 claimant: the job being granted is in neither set here).
            want = (
                len(self._free)
                if not self._waiting
                else self._fair_share(extra_claimants=1)
            )
            grant = self._pick_planes(job, max(want, self.min_planes))
            self._grant(job, grant, granted)
        if granted:
            self._plan_granted(granted)
        if self._waiting:
            self._request_shrinks()
        elif self._free and self.rebalance and self._running:
            self._offer_grow()

    def _pick_planes(self, job: _Job, k: int) -> tuple[int, ...]:
        """Choose ``k`` free planes for a new lease under ``placement``."""
        if self.placement == "schedule_aware":
            # Prefer planes whose installed circuit already matches the
            # job's next-step config in its namespace: a co-located
            # same-key tenant starts hot (and hits the same plan-cache
            # key as its predecessors).  Ties fall back to lowest id.
            want = (job.key, job.pattern.steps[job.step_idx].config)
            ranked = sorted(
                self._free,
                key=lambda p: (self._plane_state[p] != want, p),
            )
            return tuple(sorted(ranked[:k]))
        return tuple(sorted(self._free))[:k]

    def _request_shrinks(self) -> None:
        """Ask over-share running jobs to release planes at their next
        step boundary (lazy revocation; nothing happens mid-transmission)."""
        share = self._fair_share()
        for job in sorted(self._running.values(), key=lambda j: j.job_id):
            target = max(self.min_planes, share)
            if len(job.planes) > target:
                job.target_planes = target

    def _offer_grow(self) -> None:
        """Reserve all free planes for the running job with the smallest
        lease; it absorbs them (and re-plans) at its next step boundary."""
        job = min(
            self._running.values(), key=lambda j: (len(j.planes), j.job_id)
        )
        extra = tuple(sorted(self._free))
        self._free.clear()
        job.pending_planes = tuple(sorted(job.pending_planes + extra))
        job.target_planes = len(job.planes) + len(job.pending_planes)

    # -- lease lifecycle ----------------------------------------------------
    def _grant(
        self,
        job: _Job,
        planes: tuple[int, ...],
        deferred: list[_Job] | None = None,
    ) -> None:
        now = self.engine.now
        self._free.difference_update(planes)
        job.planes = tuple(sorted(planes))
        job.target_planes = len(job.planes)
        job.record.start = now
        job.record.planes_min = len(job.planes)
        job.record.planes_max = len(job.planes)
        self._running[job.job_id] = job
        self.stats.admitted += 1
        if self.tracer.enabled:
            self.tracer.instant(
                "lease_grant",
                now,
                job=job.job_id,
                tag=job.record.tag,
                planes=list(job.planes),
                queueing_delay=now - job.record.arrival,
            )
            self._trace_gauges()
        if self._m_on:
            self._m_queue_wait.labels(job.record.tenant).observe(
                now - job.record.arrival
            )
            self._m_lease_planes.observe(len(job.planes))
            job.lease_since = now
        if deferred is None:
            self._plan(job)
        else:
            deferred.append(job)

    def _sub_fabric(
        self, job: _Job, planes: tuple[int, ...] | None = None
    ) -> OpticalFabric:
        planes = job.planes if planes is None else planes
        scales = None
        if self.fabric.plane_bandwidth_scale is not None:
            scales = tuple(
                self.fabric.plane_bandwidth_scale[p] for p in planes
            )
        return OpticalFabric(
            n_nodes=self.fabric.n_nodes,
            n_planes=len(planes),
            bandwidth=self.fabric.bandwidth,
            t_recfg=self.fabric.t_recfg,
            plane_bandwidth_scale=scales,
            initial_configs=self._init_configs(job.key, planes),
        )

    def _init_configs(
        self, key: ConfigKey, planes: tuple[int, ...] | list[int]
    ) -> tuple[int | None, ...]:
        """Installed configs visible to ``key``'s namespace, per plane."""
        return tuple(
            state[1]
            if (state := self._plane_state[p]) is not None
            and state[0] == key
            else None
            for p in planes
        )

    def _lease_frame(
        self, planes: tuple[int, ...], now: float
    ) -> tuple[float, tuple[float, ...]]:
        """Plan-frame origin + per-plane ready offsets for a lease.

        The plan starts when the *earliest* leased plane frees (never
        before ``now``); later planes enter with positive ready offsets
        instead of stalling the whole sub-schedule to the latest one.
        """
        ready_abs = [self._plane_free_at[p] for p in planes]
        t0 = max(now, min(ready_abs)) if ready_abs else now
        return t0, tuple(max(0.0, r - t0) for r in ready_abs)

    # -- planning -----------------------------------------------------------
    def _plan_key(
        self, job: _Job, plane_ready: tuple[float, ...]
    ) -> tuple:
        """Everything a plan depends on besides the cache's bound fabric
        signature (n_nodes / bandwidth / t_recfg)."""
        scales = self.fabric.plane_bandwidth_scale
        return (
            job.req.algorithm,
            job.req.n_nodes,
            job.req.size,
            job.step_idx,
            job.method,
            job.mode,
            len(job.planes),
            tuple(scales[p] for p in job.planes)
            if scales is not None
            else None,
            self._init_configs(job.key, job.planes),
            plane_ready,
        )

    def _build_plan(
        self, job: _Job, plane_ready: tuple[float, ...]
    ) -> CachedPlan:
        """Plan ``job``'s remaining steps on its current lease (a miss)."""
        remaining = job.pattern.steps[job.step_idx :]
        assert remaining, "planning a finished job"
        sub_pattern = Pattern(
            job.pattern.name, job.pattern.n_nodes, tuple(remaining)
        )
        schedule, _method = swot_schedule(
            self._sub_fabric(job),
            sub_pattern,
            method=job.method,
            mode=job.mode,
            plane_ready=plane_ready,
        )
        return CachedPlan(
            schedule, _rel_bounds(job.mode, schedule, len(remaining))
        )

    def _install_plan(
        self, job: _Job, cached: CachedPlan, t0: float
    ) -> None:
        """Attach a (possibly cached) plan to ``job``, time-shifted to
        ``t0``, and schedule its next boundary."""
        job.plan = cached.schedule
        job.cached = cached
        job.plan_base_step = job.step_idx
        job.plan_t0 = t0
        job.boundaries = tuple(t0 + r for r in cached.boundaries_rel)
        if job.planned:  # only lease-change re-plans count
            self.stats.replans += 1
            job.record.replans += 1
        job.planned = True
        self._schedule_boundary(job)

    def _plan(self, job: _Job) -> None:
        """(Re)schedule ``job``'s remaining steps on its current lease."""
        now = self.engine.now
        t0, plane_ready = self._lease_frame(job.planes, now)
        if self._cache is None:
            self._install_plan(job, self._build_plan(job, plane_ready), t0)
            return
        key = self._plan_key(job, plane_ready)
        cached = self._cache.lookup(key)
        if cached is None:
            t_wall = time.perf_counter()
            cached = self._build_plan(job, plane_ready)
            self._cache.insert(
                key, cached, time.perf_counter() - t_wall
            )
        self._install_plan(job, cached, t0)

    def _plan_granted(self, jobs: list[_Job]) -> None:
        """Plan every lease granted in one ``_drain_queue`` pass.

        Cache hits install immediately; two or more *misses* that the
        instance-batched greedy can serve (greedy CHAIN, no ready
        offsets) are planned through ONE ``swot_greedy_chain_batch``
        pass -- bitwise-identical schedules to the per-job path on
        IEEE-float64 platforms; not on a TPU once the batch takes the
        fused planner (ROADMAP speed item 3) -- and
        everything else falls back to per-job planning.  Plans install in
        grant order, so boundary events keep the legacy tie-break order.
        """
        assert self._cache is not None
        now = self.engine.now
        hits: dict[int, tuple[float, CachedPlan]] = {}
        misses: dict[int, tuple[float, tuple, tuple[float, ...]]] = {}
        for job in jobs:
            t0, plane_ready = self._lease_frame(job.planes, now)
            key = self._plan_key(job, plane_ready)
            cached = self._cache.lookup(key)
            if cached is not None:
                hits[job.job_id] = (t0, cached)
            else:
                misses[job.job_id] = (t0, key, plane_ready)
        # One grid pass for the batchable misses (deduped by key: equal
        # keys would plan the identical cell twice).
        batch: list[tuple[_Job, tuple, tuple[float, ...]]] = []
        seen_keys: set = set()
        for job in jobs:
            entry = misses.get(job.job_id)
            if entry is None:
                continue
            _t0, key, ready = entry
            if (
                job.method == "greedy"
                and job.mode is DependencyMode.CHAIN
                and not any(r > 0.0 for r in ready)
                and key not in seen_keys
            ):
                seen_keys.add(key)
                batch.append((job, key, ready))
        if len(batch) >= 2:
            t_wall = time.perf_counter()
            cells = []
            readies = []
            for job, _key, ready in batch:
                remaining = job.pattern.steps[job.step_idx :]
                cells.append(
                    (
                        self._sub_fabric(job),
                        Pattern(
                            job.pattern.name,
                            job.pattern.n_nodes,
                            tuple(remaining),
                        ),
                    )
                )
                readies.append(ready)
            schedules = swot_greedy_chain_batch(cells, plane_ready=readies)
            wall = (time.perf_counter() - t_wall) / len(batch)
            for (job, key, _ready), schedule in zip(batch, schedules):
                n_steps = job.pattern.n_steps - job.step_idx
                self._cache.insert(
                    key,
                    CachedPlan(
                        schedule, _rel_bounds(job.mode, schedule, n_steps)
                    ),
                    wall,
                )
        for job in jobs:
            if job.job_id in hits:
                t0, cached = hits[job.job_id]
            else:
                t0, key, ready = misses[job.job_id]
                cached = self._cache.peek(key)  # batch result or dupe key
                if cached is None:
                    t_wall = time.perf_counter()
                    cached = self._build_plan(job, ready)
                    self._cache.insert(
                        key, cached, time.perf_counter() - t_wall
                    )
            self._install_plan(job, cached, t0)

    def _schedule_boundary(self, job: _Job) -> None:
        k = job.step_idx - job.plan_base_step
        if job.mode is DependencyMode.INDEPENDENT:
            k = 0
        self.engine.at(
            job.boundaries[k], lambda job=job: self._on_boundary(job)
        )

    def _on_boundary(self, job: _Job) -> None:
        now = self.engine.now
        if job.mode is DependencyMode.INDEPENDENT:
            job.step_idx = job.pattern.n_steps
        else:
            job.step_idx += 1
        if job.step_idx >= job.pattern.n_steps:
            self._complete(job)
            return
        wants_resize = (
            job.target_planes != len(job.planes) or job.pending_planes
        )
        if wants_resize:
            self._apply_resize(job, now)
        else:
            self._schedule_boundary(job)

    # -- backend selection --------------------------------------------------
    def _select_backend(self, n_candidates: int) -> str | None:
        """IR backend for a batched re-scoring of ``n_candidates`` rows.

        An explicit arbiter ``backend`` always wins.  Otherwise the jax
        backend is auto-selected once the candidate batch reaches
        ``REPRO_ARBITER_BACKEND_THRESHOLD`` rows (default
        ``_DEFAULT_BACKEND_THRESHOLD``) -- the shared
        `repro.core.ir.backends.select_backend_by_size` policy, which the
        grid planners apply with their own threshold env too.
        """
        return select_backend_by_size(
            n_candidates,
            ENV_BACKEND_THRESHOLD,
            _DEFAULT_BACKEND_THRESHOLD,
            explicit=self.backend,
        )

    # -- plan surgery -------------------------------------------------------
    def _cut_plan(self, job: _Job, cutoff: float) -> None:
        """Retire ``job``'s plan at ``cutoff``: account activities that
        (already) ran, update physical plane state, discard the rest.

        An in-flight reconfiguration (start < cutoff <= end) completes --
        optics cannot abort a mirror move halfway -- so the plane's config
        becomes its target and the plane stays busy until its end.

        Full retirement (``cutoff`` at the final boundary, i.e. job
        completion) with tracing off applies the plan's precomputed
        per-plane summary in O(planes) -- same floats as the walk below
        (the summary accumulates in the identical order; see
        ``CachedPlan.retirement``).  Partial cuts and traced runs walk
        the per-plane activity lists, which the plan sorts once instead
        of once per event.
        """
        assert job.plan is not None and job.cached is not None
        trace = self.tracer.enabled
        rec = job.record
        n_p = len(job.planes)
        if (
            self._cache is not None
            and not trace
            and cutoff >= job.boundaries[-1]
        ):
            plan_t0 = job.plan_t0
            for j, p in enumerate(job.planes):
                ret = job.cached.retirement()[j]
                if ret.final_config is not None:
                    self._plane_state[p] = (job.key, ret.final_config)
                free_at = self._plane_free_at[p]
                if ret.max_end_rel is not None:
                    end_abs = plan_t0 + ret.max_end_rel
                    if end_abs > free_at:
                        free_at = end_abs
                self._plane_free_at[p] = max(free_at, cutoff)
                self.stats.plane_busy[p] = (
                    self.stats.plane_busy.get(p, 0.0) + ret.busy
                )
                self.stats.reconfigurations += ret.recfgs
                # Plane-mean attribution: identical per-plane sums and
                # fold order as the walk below (see CachedPlan docs).
                rec.t_xmit += ret.xmit / n_p
                rec.t_bypass += ret.bypass / n_p
                rec.t_recfg_exposed += ret.exposed / n_p
                rec.t_recfg_hidden += ret.hidden / n_p
            job.plan = None
            job.cached = None
            return
        sub_fabric = job.plan.fabric
        rel_cutoff = cutoff - job.plan_t0  # plan times are plan-relative
        barriers = job.cached.barriers()
        chain = job.mode is DependencyMode.CHAIN
        for j, p in enumerate(job.planes):
            config = sub_fabric.initial_config(j)
            free_at = self._plane_free_at[p]
            busy = 0.0
            recfgs = 0
            xmit = bypass = exposed = hidden = 0.0
            for a in job.cached.plane_activities(j):
                if a.start >= rel_cutoff - _EPS:
                    continue  # never started: the re-plan supersedes it
                if a.kind is Kind.RECFG:
                    config = a.config
                    recfgs += 1
                    dur = a.duration
                    if chain:
                        b = barriers[a.step]
                        wait = min(
                            max(max(b, a.end) - max(b, a.start), 0.0), dur
                        )
                    else:
                        wait = dur
                    exposed += wait
                    hidden += dur - wait
                elif a.route >= 0:
                    bypass += a.duration
                else:
                    xmit += a.duration
                busy += a.duration
                free_at = max(free_at, job.plan_t0 + a.end)
                if trace:
                    # Retired activities are the ones that actually ran:
                    # emitting here (not at plan time) means superseded
                    # plan tails never pollute the trace.  Thread row =
                    # the *physical* plane id, so concurrent jobs
                    # interleave on shared rows exactly as the fabric
                    # executed them.
                    if a.kind is Kind.RECFG:
                        name = f"reconfig->c{a.config}"
                    elif a.route >= 0:
                        name = f"bypass r{a.route}h{a.hop}"
                    else:
                        name = f"{job.record.tag} s{job.plan_base_step + a.step}"
                    self.tracer.span(
                        name,
                        job.plan_t0 + a.start,
                        job.plan_t0 + a.end,
                        tid=p,
                        job=job.job_id,
                        step=job.plan_base_step + a.step,
                    )
            if config is not None:
                self._plane_state[p] = (job.key, config)
            self._plane_free_at[p] = max(free_at, cutoff)
            self.stats.plane_busy[p] = (
                self.stats.plane_busy.get(p, 0.0) + busy
            )
            self.stats.reconfigurations += recfgs
            rec.t_xmit += xmit / n_p
            rec.t_bypass += bypass / n_p
            rec.t_recfg_exposed += exposed / n_p
            rec.t_recfg_hidden += hidden / n_p
        job.plan = None
        job.cached = None

    def _cut_preview(
        self, job: _Job, cutoff: float
    ) -> tuple[dict[int, float], dict[int, tuple[ConfigKey, int]]]:
        """Read-only ``_cut_plan``: the (free_at, plane_state) a job's
        leased planes will carry after its cut at ``cutoff``.

        Used to score another job's lease shrink *before* its boundary
        event fires (the shared-boundary batched re-scoring); runs the
        identical activity walk, so predicted values match the eventual
        mutation bit for bit.
        """
        assert job.plan is not None and job.cached is not None
        sub_fabric = job.plan.fabric
        rel_cutoff = cutoff - job.plan_t0
        free: dict[int, float] = {}
        state: dict[int, tuple[ConfigKey, int]] = {}
        for j, p in enumerate(job.planes):
            config = sub_fabric.initial_config(j)
            free_at = self._plane_free_at[p]
            for a in job.cached.plane_activities(j):
                if a.start >= rel_cutoff - _EPS:
                    continue
                if a.kind is Kind.RECFG:
                    config = a.config
                free_at = max(free_at, job.plan_t0 + a.end)
            if config is not None:
                state[p] = (job.key, config)
            free[p] = max(free_at, cutoff)
        return free, state

    # -- lease-shrink re-scoring --------------------------------------------
    def _lease_profile(
        self,
        key: ConfigKey,
        lease_sorted: list[int],
        rel_free: tuple[float, ...],
        state_of,
    ) -> tuple:
        """Canonical lease profile: per plane (unclamped free offset,
        bandwidth scale, installed config visible to ``key``), in
        plane-id order.

        Two physically different leases with equal profiles score
        identically (plane ids only label the rows), which is the
        memoization key for release choices.
        """
        scales = self.fabric.plane_bandwidth_scale
        return tuple(
            (
                rel_free[i],
                scales[p] if scales is not None else 1.0,
                st[1]
                if (st := state_of(p)) is not None and st[0] == key
                else None,
            )
            for i, p in enumerate(lease_sorted)
        )

    def _release_rows(
        self,
        prof: tuple,
        candidates: list[tuple[int, ...]],
        sub_pattern: Pattern,
    ) -> tuple[list[BatchInstance], list[float], list[tuple[float, ...]]]:
        """One strawman-estimate row per candidate release set."""
        scales_on = self.fabric.plane_bandwidth_scale is not None
        instances: list[BatchInstance] = []
        starts: list[float] = []
        readies: list[tuple[float, ...]] = []
        for release in candidates:
            # Kept rows stay in profile (plane-id) order, the order the
            # legacy path built sub-fabrics in.  Offsets are unclamped
            # lease-relative; the frame origin clamps to "now" (0.0).
            kept = [i for i in range(len(prof)) if i not in release]
            rels = [prof[i][0] for i in kept]
            t0_rel = max(0.0, min(rels))
            fab = OpticalFabric(
                n_nodes=self.fabric.n_nodes,
                n_planes=len(kept),
                bandwidth=self.fabric.bandwidth,
                t_recfg=self.fabric.t_recfg,
                plane_bandwidth_scale=(
                    tuple(prof[i][1] for i in kept) if scales_on else None
                ),
                initial_configs=tuple(prof[i][2] for i in kept),
            )
            instances.append(strawman_instance(fab, sub_pattern))
            starts.append(t0_rel)
            readies.append(
                tuple(max(0.0, r - t0_rel) for r in rels)
            )
        return instances, starts, readies

    def _choose_release(
        self, job: _Job, lease: list[int], n_release: int, now: float
    ) -> tuple[int, ...]:
        """Pick which planes a shrinking job releases.

        Candidate release sets (the historical soonest-free choice plus up
        to ``_MAX_RELEASE_CANDIDATES`` alternatives) are re-scored in ONE
        ``batch_evaluate`` pass: each kept-set is evaluated as a sub-fabric
        with per-plane ready offsets under a proportional-split estimate of
        the job's remaining steps, and the candidate with the earliest
        estimated finish wins (ties keep the historical choice).

        Candidates, frames and scoring all live in lease-*relative* time
        over a canonical plane-id-ordered profile, so the choice is a pure
        function of (job signature, remaining step, profile) -- memoizable
        -- and, on a miss, every other shrink due at this exact timestamp
        is scored in the same ``batch_evaluate`` call (the shared-boundary
        batching; predictions that turn stale simply miss and re-score).
        """
        by_free = sorted(lease, key=lambda p: (self._plane_free_at[p], p))
        default = tuple(by_free[:n_release])
        if job.step_idx >= job.pattern.n_steps or n_release <= 0:
            return default
        lease_sorted = sorted(lease)
        # Unclamped lease-relative free offsets: subtracting one shared
        # "now" preserves the absolute ordering bit for bit (reserved
        # grow planes may be long idle, i.e. negative), while making the
        # profile -- and hence the memo key -- grant-instant-invariant.
        rel_free = tuple(
            self._plane_free_at[p] - now for p in lease_sorted
        )
        prof = self._lease_profile(
            job.key, lease_sorted, rel_free, self._plane_state.get
        )
        candidates = _release_candidates(prof, n_release)
        if len(candidates) == 1:
            return default
        backend = self._select_backend(len(candidates))
        sub_pattern = Pattern(
            job.pattern.name,
            job.pattern.n_nodes,
            tuple(job.pattern.steps[job.step_idx :]),
        )
        if self._cache is None:
            instances, starts, readies = self._release_rows(
                prof, candidates, sub_pattern
            )
            result = batch_evaluate(
                instances, plane_ready=readies, backend=backend
            )
            best = _pick_best(
                candidates, starts, result.cct, result.feasible, 0
            )
            return tuple(lease_sorted[i] for i in candidates[best])
        key = (
            job.req.algorithm,
            job.req.n_nodes,
            job.req.size,
            job.step_idx,
            n_release,
            prof,
            backend,
        )
        choice = self._cache.release_lookup(key)
        if choice is None:
            self._score_releases_batched(
                key, sub_pattern, prof, candidates, backend, job, now
            )
            choice = self._cache.peek_release(key)
            assert choice is not None
        return tuple(lease_sorted[i] for i in choice)

    def _score_releases_batched(
        self,
        key: tuple,
        sub_pattern: Pattern,
        prof: tuple,
        candidates: list[tuple[int, ...]],
        backend: str | None,
        job: _Job,
        now: float,
    ) -> None:
        """Score this shrink -- and every same-backend shrink due at this
        exact timestamp -- in ONE ``batch_evaluate`` call.

        Peers' inputs are *predicted* (post-cut plane state via
        ``_cut_preview``, next step, current shrink target); a prediction
        invalidated by intervening grants/regrows simply never matches the
        peer's eventual key and it re-scores solo -- so batching can only
        save work, never change a choice.
        """
        group: list[
            tuple[tuple, Pattern, tuple, list[tuple[int, ...]], bool]
        ] = [(key, sub_pattern, prof, candidates, False)]
        for peer in self._due_shrink_peers(job, now):
            pkey, psub, pprof, pcands = peer
            if pkey[-1] != backend or pkey == key:
                continue
            if self._cache.peek_release(pkey) is not None:
                continue
            group.append((pkey, psub, pprof, pcands, True))
        all_instances: list[BatchInstance] = []
        all_readies: list[tuple[float, ...]] = []
        spans: list[tuple[tuple, list[tuple[int, ...]], list[float], int, bool]] = []
        for gkey, gsub, gprof, gcands, prefetched in group:
            instances, starts, readies = self._release_rows(
                gprof, gcands, gsub
            )
            spans.append(
                (gkey, gcands, starts, len(all_instances), prefetched)
            )
            all_instances.extend(instances)
            all_readies.extend(readies)
        result = batch_evaluate(
            all_instances, plane_ready=all_readies, backend=backend
        )
        for gkey, gcands, starts, offset, prefetched in spans:
            best = _pick_best(
                gcands, starts, result.cct, result.feasible, offset
            )
            self._cache.release_insert(
                gkey, gcands[best], prefetched=prefetched
            )

    def _due_shrink_peers(
        self, job: _Job, now: float
    ) -> list[tuple[tuple, Pattern, tuple, list[tuple[int, ...]]]]:
        """Predicted (key, sub_pattern, profile, candidates) for every
        other running job whose boundary fires at exactly ``now`` and
        that will shrink-score there."""
        peers = []
        for other in sorted(
            self._running.values(), key=lambda x: x.job_id
        ):
            if (
                other.job_id == job.job_id
                or other.plan is None
                or other.mode is DependencyMode.INDEPENDENT
            ):
                continue
            k = other.step_idx - other.plan_base_step
            if other.boundaries[k] != now:
                continue
            step_next = other.step_idx + 1
            if step_next >= other.pattern.n_steps:
                continue  # completes at this boundary: no resize
            lease = sorted(other.planes + other.pending_planes)
            if other.target_planes >= len(lease):
                continue  # grow or steady: no shrink scoring
            n_release = len(lease) - max(
                other.target_planes, self.min_planes
            )
            if n_release <= 0:
                continue
            free_pred, state_pred = self._cut_preview(other, now)
            rel_free = tuple(
                free_pred.get(p, self._plane_free_at[p]) - now
                for p in lease
            )
            prof = self._lease_profile(
                other.key,
                lease,
                rel_free,
                lambda p: state_pred.get(p, self._plane_state[p]),
            )
            cands = _release_candidates(prof, n_release)
            if len(cands) == 1:
                continue
            backend = self._select_backend(len(cands))
            pkey = (
                other.req.algorithm,
                other.req.n_nodes,
                other.req.size,
                step_next,
                n_release,
                prof,
                backend,
            )
            psub = Pattern(
                other.pattern.name,
                other.pattern.n_nodes,
                tuple(other.pattern.steps[step_next:]),
            )
            peers.append((pkey, psub, prof, cands))
        return peers

    def _apply_resize(self, job: _Job, now: float) -> None:
        before = job.planes
        self._cut_plan(job, now)
        # Absorb reserved grow planes first, then shrink to target.
        lease = sorted(job.planes + job.pending_planes)
        job.pending_planes = ()
        if job.target_planes < len(lease):
            n_release = len(lease) - max(job.target_planes, self.min_planes)
            for p in self._choose_release(job, lease, n_release, now):
                lease.remove(p)
                self._free.add(p)
        job.planes = tuple(sorted(lease))
        if self.tracer.enabled and job.planes != before:
            kind = "lease_grow" if len(job.planes) > len(before) else (
                "lease_shrink"
            )
            self.tracer.instant(
                kind,
                now,
                job=job.job_id,
                tag=job.record.tag,
                planes_before=list(before),
                planes_after=list(job.planes),
            )
            self._trace_gauges()
        job.target_planes = len(job.planes)
        job.record.planes_min = min(job.record.planes_min, len(job.planes))
        job.record.planes_max = max(job.record.planes_max, len(job.planes))
        if self._m_on and job.planes != before:
            self._m_lease_planes.observe(len(job.planes))
            self._m_lease_s.labels(job.record.tenant).observe(
                now - job.lease_since
            )
            job.lease_since = now
        self._plan(job)
        self._drain_queue()

    def _complete(self, job: _Job) -> None:
        now = self.engine.now
        self._cut_plan(job, now)  # every activity started strictly before now
        rec = job.record
        rec.finish = now
        # Close the live attribution: t_idle is the exact complement of
        # the accumulated components against the CCT (same ulp-refined
        # construction as obs.attribution.closing_idle, scalar form).
        cct = now - rec.start
        comp = (
            (rec.t_xmit + rec.t_bypass) + rec.t_recfg_exposed
        ) + rec.t_recfg_hidden
        idle = cct - comp
        for _ in range(4):
            err = cct - (comp + idle)
            if err == 0.0:
                break
            idle += err
        rec.t_idle = idle
        self.stats.completed += 1
        del self._running[job.job_id]
        self._free.update(job.planes)
        self._free.update(job.pending_planes)
        job.planes = ()
        job.pending_planes = ()
        if self.tracer.enabled:
            self.tracer.instant(
                "job_complete",
                now,
                job=job.job_id,
                tag=rec.tag,
                cct=rec.cct,
                replans=rec.replans,
            )
        if self._m_on:
            tenant = rec.tenant
            self._m_completed.labels(tenant).inc()
            self._m_bytes.labels(tenant).inc(rec.size)
            self._m_cct.labels(tenant).observe(cct)
            self._m_lease_s.labels(tenant).observe(now - job.lease_since)
            site = rec.site
            self._m_site_jobs.labels(tenant, site).inc()
            self._m_site_cct.labels(tenant, site).inc(cct)
            self._m_site_xmit.labels(tenant, site).inc(rec.t_xmit)
            self._m_site_bypass.labels(tenant, site).inc(rec.t_bypass)
            self._m_site_exposed.labels(tenant, site).inc(
                rec.t_recfg_exposed
            )
            self._m_site_hidden.labels(tenant, site).inc(
                rec.t_recfg_hidden
            )
            if rec.t_idle >= 0.0:
                self._m_site_idle.labels(tenant, site).inc(rec.t_idle)
        if self.record_sink is not None:
            self.record_sink(rec)
        self._drain_queue()
        if self.tracer.enabled:
            self._trace_gauges()
        if self._m_on:
            self._metric_gauges()

    # -- introspection ------------------------------------------------------
    @property
    def running_jobs(self) -> tuple[int, ...]:
        return tuple(sorted(self._running))

    @property
    def queue_depth(self) -> int:
        return len(self._waiting)

    def assert_invariants(self) -> None:
        """Every plane is free XOR leased/reserved by exactly one job."""
        owned: dict[int, int] = {}
        for job in self._running.values():
            for p in job.planes + job.pending_planes:
                if p in owned:
                    raise AssertionError(
                        f"plane {p} owned by jobs {owned[p]} and "
                        f"{job.job_id}"
                    )
                owned[p] = job.job_id
        overlap = self._free & set(owned)
        if overlap:
            raise AssertionError(f"planes {overlap} both free and leased")
        missing = (
            set(range(self.fabric.n_planes)) - self._free - set(owned)
        )
        if missing:
            raise AssertionError(f"planes {missing} unaccounted for")
