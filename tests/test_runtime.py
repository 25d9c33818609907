"""Tests for the multi-tenant optical runtime (engine + arbiter + workload)."""

import pytest

from repro.configs.registry import get_config
from repro.core import (
    CollectiveRequest,
    OpticalController,
    OpticalFabric,
    SwotShim,
    get_pattern,
    swot_schedule,
)
from repro.runtime import (
    FabricArbiter,
    SimEngine,
    arch_request_mix,
    poisson_trace,
    replay,
)


# -- engine ----------------------------------------------------------------
def test_engine_orders_events_and_breaks_ties_by_schedule_order():
    engine = SimEngine()
    fired = []
    engine.at(2.0, lambda: fired.append("late"))
    engine.at(1.0, lambda: fired.append("early"))
    engine.at(1.0, lambda: fired.append("early2"))  # same time: FIFO
    engine.run()
    assert fired == ["early", "early2", "late"]
    assert engine.now == 2.0


def test_engine_cancellation_and_run_until():
    engine = SimEngine()
    fired = []
    handle = engine.at(1.0, lambda: fired.append("cancelled"))
    engine.at(2.0, lambda: fired.append("kept"))
    handle.cancel()
    engine.run(until=1.5)
    assert fired == [] and engine.now == 1.5
    engine.run()
    assert fired == ["kept"]


def test_engine_rejects_past_events():
    engine = SimEngine()
    engine.at(1.0, lambda: None)
    engine.run()
    with pytest.raises(ValueError):
        engine.at(0.5, lambda: None)


# -- arbiter: single-tenant degenerate case --------------------------------
@pytest.mark.parametrize(
    "algorithm,n,size",
    [
        ("rabenseifner_allreduce", 8, 40e6),
        ("pairwise_alltoall", 8, 16e6),
        ("ring_allreduce", 8, 8e6),
    ],
)
def test_single_tenant_runtime_cct_matches_serial_scheduler(
    algorithm, n, size
):
    """With a whole-fabric lease the arbiter realizes exactly the CCT the
    serial scheduler (and hence ``cct_of`` on its decisions) computes."""
    fabric = OpticalFabric(n, 4)
    req = CollectiveRequest(algorithm, n, size, "solo")
    pattern = get_pattern(algorithm, n, size)
    ref_schedule, _ = swot_schedule(
        fabric.prestaged(pattern.steps[0].config), pattern, method="greedy"
    )
    engine = SimEngine()
    arbiter = FabricArbiter(engine, fabric, method="greedy")
    arbiter.prestage(req)
    record = arbiter.run_collective(req)
    assert record.queueing_delay == 0.0
    assert record.cct == pytest.approx(ref_schedule.cct, abs=1e-9)
    arbiter.assert_invariants()


def test_shim_through_runtime_matches_serial_clock_single_tenant():
    fabric = OpticalFabric(8, 4)
    req = CollectiveRequest("rabenseifner_allreduce", 8, 40e6, "g")

    serial = SwotShim(fabric, method="greedy")
    serial.install([req])
    serial.intercept(req)

    engine = SimEngine()
    arbiter = FabricArbiter(engine, fabric, method="greedy")
    arbiter.prestage(req)
    routed = SwotShim(
        fabric,
        controller=OpticalController(fabric, runtime=arbiter),
        method="greedy",
    )
    routed.install([req])
    routed.intercept(req)
    assert routed.controller.clock == pytest.approx(
        serial.controller.clock, abs=1e-9
    )


# -- arbiter: concurrency --------------------------------------------------
def _two_job_arbiter(n_planes=4):
    fabric = OpticalFabric(8, n_planes)
    engine = SimEngine()
    arbiter = FabricArbiter(engine, fabric, method="greedy")
    r1 = arbiter.submit(
        CollectiveRequest("rabenseifner_allreduce", 8, 40e6, "a")
    )
    r2 = arbiter.submit(CollectiveRequest("pairwise_alltoall", 8, 20e6, "b"))
    return engine, arbiter, r1, r2


def test_two_concurrent_jobs_share_planes_and_both_complete():
    engine, arbiter, r1, r2 = _two_job_arbiter()
    engine.run()
    arbiter.assert_invariants()
    assert r1.finish is not None and r2.finish is not None
    # The late job had to wait for a lease (first job held all planes).
    assert r2.queueing_delay > 0
    # The first job shrank its lease to make room.
    assert r1.planes_min < r1.planes_max


def test_plane_lease_invariant_holds_at_every_event():
    engine, arbiter, _, _ = _two_job_arbiter()
    # Heavier contention: four more arrivals while the first two run.
    for i in range(4):
        engine.at(
            1e-4 * (i + 1),
            lambda i=i: arbiter.submit(
                CollectiveRequest("ring_allreduce", 8, 10e6, f"x{i}")
            ),
        )
    while engine.step():
        arbiter.assert_invariants()
    assert arbiter.stats.completed == 6


def test_deterministic_event_ordering_across_replays():
    def one_run():
        engine, arbiter, r1, r2 = _two_job_arbiter()
        engine.run()
        return [
            (r.start, r.finish, r.planes_min, r.planes_max)
            for r in (r1, r2)
        ]

    assert one_run() == one_run()


def test_priorities_order_the_admission_queue():
    fabric = OpticalFabric(8, 2)
    engine = SimEngine()
    arbiter = FabricArbiter(engine, fabric, method="greedy")
    # Fill the fabric, then queue one low- and one high-priority job.
    arbiter.submit(CollectiveRequest("rabenseifner_allreduce", 8, 40e6, "bg"))
    lo = arbiter.submit(
        CollectiveRequest("ring_allreduce", 8, 5e6, "lo"), priority=0
    )
    hi = arbiter.submit(
        CollectiveRequest("ring_allreduce", 8, 5e6, "hi"), priority=10
    )
    engine.run()
    assert hi.start < lo.start


def test_backpressure_rejects_when_queue_full():
    fabric = OpticalFabric(8, 2)
    engine = SimEngine()
    arbiter = FabricArbiter(
        engine, fabric, method="greedy", max_queue_depth=1
    )
    arbiter.submit(CollectiveRequest("rabenseifner_allreduce", 8, 40e6, "r"))
    arbiter.submit(CollectiveRequest("ring_allreduce", 8, 5e6, "q"))
    rejected = arbiter.submit(
        CollectiveRequest("ring_allreduce", 8, 5e6, "drop")
    )
    assert rejected.rejected
    assert arbiter.stats.rejected == 1
    engine.run()
    assert arbiter.stats.completed == 2


def test_same_algorithm_jobs_reuse_installed_circuits():
    """Back-to-back jobs of one (algorithm, n) share the config namespace:
    the second run starts with hot circuits and matches the first's CCT."""
    fabric = OpticalFabric(8, 4)
    engine = SimEngine()
    arbiter = FabricArbiter(engine, fabric, method="greedy")
    req = CollectiveRequest("ring_allreduce", 8, 8e6, "it")
    arbiter.prestage(req)
    first = arbiter.run_collective(req)
    second = arbiter.run_collective(req)
    assert second.cct == pytest.approx(first.cct, abs=1e-9)


# -- shim regressions ------------------------------------------------------
def test_shim_misses_stay_zero_on_preinstalled_workloads():
    fabric = OpticalFabric(16, 4)
    shim = SwotShim(fabric, method="greedy")
    reqs = [
        CollectiveRequest("rabenseifner_allreduce", 16, 25e6, "dp"),
        CollectiveRequest("pairwise_alltoall", 16, 8e6, "moe"),
        CollectiveRequest("all_gather", 16, 12e6, "fsdp"),
    ]
    shim.install(reqs)
    for _ in range(5):
        for r in reqs:
            shim.intercept(r)
    assert shim.misses == 0
    assert shim.interceptions == 15


def test_shim_plan_cache_lru_evicts_and_recounts_miss():
    shim = SwotShim(
        OpticalFabric(8, 2), method="greedy", plan_cache_capacity=2
    )
    sizes = (1e6, 2e6, 3e6)
    for size in sizes:
        shim.intercept(CollectiveRequest("ring_allreduce", 8, size))
    assert len(shim.plans) == 2
    assert shim.evictions == 1
    # 1e6 was evicted (LRU); re-intercepting it is a fresh miss.
    misses_before = shim.misses
    shim.intercept(CollectiveRequest("ring_allreduce", 8, 1e6))
    assert shim.misses == misses_before + 1
    assert len(shim.plans) == 2


def test_shim_plan_cache_unbounded_by_default():
    shim = SwotShim(OpticalFabric(8, 2), method="greedy")
    for size in (1e6, 2e6, 3e6, 4e6):
        shim.intercept(CollectiveRequest("ring_allreduce", 8, size))
    assert len(shim.plans) == 4
    assert shim.evictions == 0


# -- workload --------------------------------------------------------------
def test_poisson_trace_is_deterministic_and_sorted():
    mix = arch_request_mix(get_config("qwen3_4b"), n_nodes=8)
    tenants = [("a", mix), ("b", mix)]
    t1 = poisson_trace(tenants, rate=20.0, horizon=0.5, seed=3)
    t2 = poisson_trace(tenants, rate=20.0, horizon=0.5, seed=3)
    assert t1 == t2
    assert all(
        t1[i].arrival <= t1[i + 1].arrival for i in range(len(t1) - 1)
    )
    assert len(t1) > 0


def test_replay_reports_per_job_and_aggregate_stats():
    mix = [
        CollectiveRequest("ring_allreduce", 8, 4e6, "sync"),
        CollectiveRequest("pairwise_alltoall", 8, 2e6, "a2a"),
    ]
    trace = poisson_trace(
        [("t0", mix), ("t1", mix)], rate=40.0, horizon=0.2, seed=11
    )
    report = replay(trace, OpticalFabric(8, 4), method="greedy")
    assert len(report.completed) == len(trace)
    assert report.makespan > 0
    assert 0 < report.utilization <= 1
    assert report.mean_cct > 0
    assert report.mean_slowdown() >= 0.99  # never faster than solo fabric
    summary = report.summary()
    assert "jobs completed" in summary and "utilization" in summary


def test_moe_config_mix_includes_alltoall():
    mix = arch_request_mix(get_config("qwen2_moe_a2_7b"), n_nodes=8)
    algs = {r.algorithm for r in mix}
    assert "pairwise_alltoall" in algs
    assert "rabenseifner_allreduce" in algs


# -- arbiter IR-backend auto-selection --------------------------------------
def test_backend_auto_selection_threshold():
    """Below the candidate threshold the arbiter stays on numpy; at/above
    it, jax is selected when importable.  The threshold must stay
    reachable: it cannot exceed the lease-shrink candidate cap, or the
    sole call site could never select jax."""
    from repro.core.ir import BackendUnavailable, get_backend
    from repro.runtime.arbiter import _JAX_FROM, _MAX_RELEASE_CANDIDATES

    assert _JAX_FROM <= _MAX_RELEASE_CANDIDATES
    arbiter = FabricArbiter(SimEngine(), OpticalFabric(8, 4))
    assert arbiter._select_backend(1) == "numpy"
    assert arbiter._select_backend(_JAX_FROM - 1) == "numpy"
    try:
        get_backend("jax")
        expected = "jax"
    except BackendUnavailable:
        expected = "numpy"
    assert arbiter._select_backend(_JAX_FROM) == expected


def test_backend_auto_selection_env_override(monkeypatch):
    from repro.core.ir import BackendUnavailable, get_backend
    from repro.runtime import arbiter as arbiter_mod

    arbiter = FabricArbiter(SimEngine(), OpticalFabric(8, 4))
    monkeypatch.setattr(arbiter_mod, "_JAX_FROM", 2)
    try:
        get_backend("jax")
        assert arbiter._select_backend(2) == "jax"
    except BackendUnavailable:
        assert arbiter._select_backend(2) == "numpy"
    assert arbiter._select_backend(1) == "numpy"
    # Past every batch: jax is never selected.
    monkeypatch.setattr(arbiter_mod, "_JAX_FROM", 10**9)
    assert arbiter._select_backend(10**6) == "numpy"


def test_backend_explicit_choice_wins_over_auto_selection(monkeypatch):
    from repro.runtime import arbiter as arbiter_mod

    arbiter = FabricArbiter(
        SimEngine(), OpticalFabric(8, 4), backend="numpy"
    )
    monkeypatch.setattr(arbiter_mod, "_JAX_FROM", 1)
    assert arbiter._select_backend(10**6) == "numpy"


def test_shrink_rescoring_runs_through_auto_selected_backend(monkeypatch):
    """End-to-end: with a threshold of 1 every lease-shrink re-scoring
    batch goes through the auto-selected backend; results (and therefore
    the shared-fabric outcome) must match the numpy-pinned run."""
    from repro.runtime import arbiter as arbiter_mod

    monkeypatch.setattr(arbiter_mod, "_JAX_FROM", 1)
    pytest.importorskip("jax")

    def run(backend):
        engine = SimEngine()
        arbiter = FabricArbiter(engine, OpticalFabric(8, 4), backend=backend)
        recs = [
            arbiter.submit(
                CollectiveRequest("rabenseifner_allreduce", 8, 40e6, "dp")
            ),
            arbiter.submit(
                CollectiveRequest("pairwise_alltoall", 8, 16e6, "moe")
            ),
            arbiter.submit(
                CollectiveRequest("ring_allreduce", 8, 8e6, "sync")
            ),
        ]
        engine.run()
        return [r.finish for r in recs]

    auto = run(backend=None)  # auto-selection (jax at threshold 1)
    pinned = run(backend="numpy")
    assert all(f is not None for f in auto)
    assert auto == pytest.approx(pinned, abs=1e-9)
