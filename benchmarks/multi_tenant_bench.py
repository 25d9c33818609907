"""Multi-tenant fabric arbitration sweep + fleet-scale runtime gate.

Replays Poisson traces of model-config-derived collectives through the
``repro.runtime`` arbiter and reports, per cell:

* mean realized CCT and mean/p95 queueing delay per job,
* mean plane utilization over the replay makespan,
* mean slowdown vs the whole-fabric solo CCT of the same collective.

The degenerate 1-tenant cell doubles as a regression anchor: with one job
in flight at a time the arbiter must realize exactly the serial
scheduler's CCT (asserted in tests/test_runtime.py; here it shows up as
slowdown 1.00x for hot circuits).

Two runtime-scale sections follow the sweep (ROADMAP item 2):

* **Parity reference** -- the canonical 19-job quick-cell trace replayed
  with the arbiter's memoized/batched path OFF (the legacy serial path)
  and ON; the two reports are asserted bit-identical in-run, and the
  legacy events/sec becomes the denominator for the speedup gate.
* **Scale** -- a 10,000-job heavy-tailed/diurnal trace
  (``heavy_tailed_trace``) replayed cold (empty plan cache; wall time
  includes all one-time planning) and warm (second replay against the
  now-populated shared cache -- the steady state a million-event serving
  run operates in).  ``mt_scale_speedup`` (warm events/sec over legacy
  events/sec, both measured in this process so the ratio is
  machine-independent) is asserted >= 50x in-run and hard-gated in
  ``check_regression.py``; the cold ratio and cache hit rate ride along.
"""

from __future__ import annotations

import re
import time

from repro.configs.registry import get_config
from repro.core import (
    BatchInstance,
    OpticalFabric,
    batch_evaluate,
    get_pattern,
    strawman_instance,
)
from repro.runtime import (
    PlanCache,
    arch_request_mix,
    heavy_tailed_trace,
    poisson_trace,
    replay,
)
from repro.obs.metrics import MetricsRegistry
from repro.obs.slo import SLOMonitor, SLOTarget
from repro.trace import overlap_comparison, replay_trace, static_trace

# Fleet-scale trace defaults (the gated 10k-job heavy-tailed replay).
_SCALE_JOBS = 10_000
_SCALE_RATE = 60.0  # arrivals/s: bursty overlap without miss blowup
_SCALE_SIGMA = 0.8  # lognormal size spread (pow2-snapped, see workload)
_SCALE_SEED = 11
# Per-tenant SLO deadline (arrival -> finish) for the scale replay's
# miss-rate rows: picked so every tenant lands strictly inside (0, 1)
# at the default scale knobs (p90..p99 responses straddle it -- the MoE
# tenant misses ~18%, the small dense tenants <1%), keeping the gated
# rates sensitive in both directions.
_SLO_DEADLINE_S = 2e-3
# Hard floor asserted in-run and gated in check_regression.py: warm
# steady-state events/sec must beat the legacy per-job planning path by
# this factor on the same machine in the same process.
_SCALE_SPEEDUP_FLOOR = 50.0

# Tenant pool: one training job per architecture family (dense, MoE).
_TENANT_ARCHS = ("qwen3_4b", "gemma_2b", "qwen2_moe_a2_7b", "qwen2_1_5b")

_N_NODES = 8
# Modest message scale keeps every cell sub-second of sim *and* wall time.
_TOKENS_PER_STEP = 16_384
_SIZE_SCALE = 1 / 256  # shrink analytic DP-sync sizes to benchmark scale
# Model-trace replays scale further: per-job transmission must be
# comparable to t_recfg (200us) for reconfiguration overlap to matter --
# the paper's operating regime.  At 1/256 the count-folded per-layer
# payloads are ~100MB+ and transmission swamps reconfiguration.
_TRACE_SIZE_SCALE = 1 / 4096


def _tenant_mixes(n_tenants: int):
    tenants = []
    for name in _TENANT_ARCHS[:n_tenants]:
        mix = arch_request_mix(
            get_config(name),
            n_nodes=_N_NODES,
            tokens_per_step=_TOKENS_PER_STEP,
        )
        mix = [
            type(r)(r.algorithm, r.n_nodes, r.size * _SIZE_SCALE, r.tag)
            for r in mix
        ]
        tenants.append((name, mix))
    return tenants


def _record_key(report):
    """Everything the bit-identical parity contract covers, per job."""
    return [
        (
            r.job_id,
            r.tag,
            r.start,
            r.finish,
            r.cct,
            r.queueing_delay,
            r.replans,
            r.planes_min,
            r.planes_max,
            r.rejected,
        )
        for r in report.records
    ]


def _assert_parity(legacy, optimized) -> None:
    """Bit-identical ``ReplayReport`` with the memoized path on vs off."""
    assert _record_key(legacy) == _record_key(optimized), (
        "memoized replay diverged from the legacy path (records)"
    )
    assert legacy.makespan == optimized.makespan
    assert legacy.stats == optimized.stats, (
        "memoized replay diverged from the legacy path (stats)"
    )
    assert legacy.events_fired == optimized.events_fired


def _close(a: float, b: float, rel: float = 1e-9) -> bool:
    return abs(a - b) <= rel * max(abs(a), abs(b), 1e-30)


def _assert_stream_parity(acc, streamed) -> None:
    """The memory-flat streamed replay must serve the accumulated
    replay's statistics from its registry: counts and means exact (float
    summation order aside), percentiles within the queue-wait
    histogram's documented error bound."""
    assert streamed.records == [], "streaming replay accumulated records"
    assert streamed.n_jobs == acc.n_jobs
    assert streamed.n_completed == acc.n_completed
    assert _close(streamed.mean_cct, acc.mean_cct)
    assert _close(streamed.mean_queueing_delay, acc.mean_queueing_delay)
    err = (
        streamed.metrics.get("fabric_queue_wait_seconds")
        .aggregate()
        .quantile_error
    )

    def in_bound(est: float, true: float) -> bool:
        return true * (1 - 1e-9) <= est <= true * (1 + err) * (1 + 1e-9)

    assert in_bound(streamed.p95_queueing_delay, acc.p95_queueing_delay)
    assert in_bound(streamed.p99_queueing_delay, acc.p99_queueing_delay)
    acc_tenants = acc.per_tenant()
    str_tenants = streamed.per_tenant()
    assert set(acc_tenants) == set(str_tenants)
    for tenant, a in acc_tenants.items():
        s = str_tenants[tenant]
        assert (s.n_jobs, s.n_completed, s.n_rejected) == (
            a.n_jobs, a.n_completed, a.n_rejected,
        )
        assert _close(s.total_bytes, a.total_bytes)
        assert _close(s.mean_cct, a.mean_cct)
        assert _close(s.mean_queueing_delay, a.mean_queueing_delay)
        assert in_bound(s.p95_queueing_delay, a.p95_queueing_delay)
        assert _close(s.overlap_efficiency, a.overlap_efficiency)


def run(
    quick: bool = False,
    jobs: int | None = None,
    arrival: float | None = None,
    tracer=None,
    metrics: MetricsRegistry | None = None,
) -> list[tuple[str, float, str]]:
    rows: list[tuple[str, float, str]] = []
    t_wall = time.perf_counter()
    # Per-phase wall-clock accounting (the ``_us``-suffixed rows below):
    # where a multi-tenant bench run actually spends its time, and the
    # replay events/sec throughput that seeds ROADMAP item 2's gate.
    t_ref_phase = t_trace_phase = t_replay_phase = 0.0
    events_total = 0
    if quick:
        cells = [(2, 4, 200e-6)]
        rate, horizon = 30.0, 0.25
    else:
        cells = [
            (n_tenants, n_planes, t_recfg)
            for n_tenants in (1, 2, 4)
            for n_planes in (2, 4, 8)
            for t_recfg in (50e-6, 200e-6)
        ]
        rate, horizon = 30.0, 0.5
    # Whole-sweep lockstep-ICR reference: every (cell, collective
    # signature) pair becomes one row of a single batched IR evaluation
    # (on the numpy timing backend, like every IR sweep by default).
    t0 = time.perf_counter()
    ref_keys: list[tuple[int, tuple]] = []
    ref_instances: list[BatchInstance] = []
    for idx, (n_tenants, n_planes, t_recfg) in enumerate(cells):
        base = OpticalFabric(_N_NODES, n_planes, t_recfg=t_recfg)
        seen = set()
        for _name, mix in _tenant_mixes(n_tenants):
            for req in mix:
                if req.signature in seen:
                    continue
                seen.add(req.signature)
                pattern = get_pattern(req.algorithm, req.n_nodes, req.size)
                ref_keys.append((idx, req.signature))
                ref_instances.append(
                    strawman_instance(base, pattern, prestage=True)
                )
    ref_ccts = batch_evaluate(ref_instances).cct
    straw_by_cell: dict[int, list[float]] = {}
    for (idx, _sig), cct in zip(ref_keys, ref_ccts):
        straw_by_cell.setdefault(idx, []).append(float(cct))
    t_ref_phase = time.perf_counter() - t0

    for idx, (n_tenants, n_planes, t_recfg) in enumerate(cells):
        fabric = OpticalFabric(_N_NODES, n_planes, t_recfg=t_recfg)
        t0 = time.perf_counter()
        trace = poisson_trace(
            _tenant_mixes(n_tenants),
            rate=rate,
            horizon=horizon,
            seed=7,
        )
        t_trace_phase += time.perf_counter() - t0
        t0 = time.perf_counter()
        report = replay(trace, fabric, method="greedy")
        t_replay_phase += time.perf_counter() - t0
        events_total += report.events_fired
        cell = (
            f"mt_t{n_tenants}_p{n_planes}_r{t_recfg * 1e6:.0f}us"
        )
        straw_ref = straw_by_cell[idx]
        mean_straw = sum(straw_ref) / len(straw_ref)
        rows.append(
            (
                f"{cell}_cct",
                report.mean_cct * 1e6,
                f"{len(report.completed)}jobs "
                f"util={report.utilization:.2f} "
                f"slowdown={report.mean_slowdown():.2f}x "
                f"straw_ref={mean_straw * 1e6:.1f}us",
            )
        )
        rows.append(
            (
                f"{cell}_queue",
                report.mean_queueing_delay * 1e6,
                f"p95={report.p95_queueing_delay * 1e6:.1f}us "
                f"replans={report.stats.replans}",
            )
        )
    rows.append(
        (
            "mt_phase_solo_ref_us",
            t_ref_phase * 1e6,
            f"{len(ref_instances)} solo-reference instances (wall)",
        )
    )
    rows.append(
        (
            "mt_phase_tracegen_us",
            t_trace_phase * 1e6,
            f"{len(cells)} cells (wall)",
        )
    )
    rows.append(
        (
            "mt_phase_replay_us",
            t_replay_phase * 1e6,
            f"{events_total} sim events (wall)",
        )
    )
    rows.append(
        (
            "mt_events_per_sec",
            events_total / t_replay_phase if t_replay_phase else 0.0,
            f"{events_total} events in {t_replay_phase * 1e3:.1f}ms "
            "of replay (wall)",
        )
    )

    # -- parity reference: canonical 19-job trace, legacy path vs hot path
    parity_fabric = OpticalFabric(_N_NODES, 4, t_recfg=200e-6)
    parity_trace = poisson_trace(
        _tenant_mixes(2), rate=30.0, horizon=0.25, seed=7
    )
    t0 = time.perf_counter()
    legacy_report = replay(
        parity_trace, parity_fabric, optimize=False, solo_refs=False
    )
    t_legacy = time.perf_counter() - t0
    optimized_report = replay(
        parity_trace, parity_fabric, optimize=True, solo_refs=False
    )
    _assert_parity(legacy_report, optimized_report)
    legacy_eps = legacy_report.events_fired / t_legacy
    rows.append(
        (
            "mt_phase_parity_legacy_us",
            t_legacy * 1e6,
            f"{legacy_report.events_fired} events at "
            f"{legacy_eps:.1f} ev/s on the legacy (optimize=False) path; "
            "bit-identical to the memoized path (asserted)",
        )
    )

    # -- fleet scale: 10k-job heavy-tailed trace, cold then warm cache
    n_jobs = jobs if jobs is not None else _SCALE_JOBS
    rate_scale = arrival if arrival is not None else _SCALE_RATE
    t0 = time.perf_counter()
    scale_trace = heavy_tailed_trace(
        _tenant_mixes(4),
        n_jobs=n_jobs,
        rate=rate_scale,
        seed=_SCALE_SEED,
        sigma=_SCALE_SIGMA,
    )
    t_scale_tracegen = time.perf_counter() - t0
    scale_fabric = OpticalFabric(_N_NODES, 4, t_recfg=200e-6)
    cache = PlanCache()
    t0 = time.perf_counter()
    cold = replay(
        scale_trace,
        scale_fabric,
        solo_refs=False,
        plan_cache=cache,
        tracer=tracer,
    )
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm = replay(
        scale_trace, scale_fabric, solo_refs=False, plan_cache=cache
    )
    t_warm = time.perf_counter() - t0
    cold_eps = cold.events_fired / t_cold
    warm_eps = warm.events_fired / t_warm
    speedup = warm_eps / legacy_eps
    speedup_cold = cold_eps / legacy_eps
    assert speedup >= _SCALE_SPEEDUP_FLOOR, (
        f"scale replay only {speedup:.1f}x the legacy path "
        f"(floor {_SCALE_SPEEDUP_FLOOR}x)"
    )
    rows.append(
        (
            "mt_scale_events_per_sec",
            cold_eps,
            f"{cold.events_fired} events, {n_jobs} heavy-tailed jobs, "
            f"cold cache ({cold.cache.misses} plan misses, "
            f"{t_cold * 1e3:.0f}ms wall incl. "
            f"{cold.cache.plan_wall_s * 1e3:.0f}ms planning)",
        )
    )
    rows.append(
        (
            "mt_scale_warm_events_per_sec",
            warm_eps,
            f"{warm.events_fired} events, warm shared cache "
            f"({t_warm * 1e3:.0f}ms wall) -- steady-state throughput",
        )
    )
    rows.append(
        (
            "mt_scale_speedup",
            speedup,
            f"warm {warm_eps:.0f} ev/s vs legacy {legacy_eps:.1f} ev/s "
            f"(same run; cold ratio {speedup_cold:.1f}x)",
        )
    )
    rows.append(
        (
            "mt_cache_hit_rate",
            cold.cache.hit_rate,
            f"{cold.cache.hits}/{cold.cache.hits + cold.cache.misses} "
            f"plan lookups hit on the cold pass; release memo "
            f"{cold.cache.release_hits}h/{cold.cache.release_misses}m",
        )
    )
    rows.append(
        (
            "mt_phase_scale_plan_us",
            cold.cache.plan_wall_s * 1e6,
            f"{cold.cache.misses} cache-miss plans (wall)",
        )
    )
    rows.append(
        (
            "mt_phase_scale_replay_us",
            max(0.0, t_cold - cold.cache.plan_wall_s) * 1e6,
            "cold-pass event loop outside planning (wall)",
        )
    )
    rows.append(
        (
            "mt_phase_scale_tracegen_us",
            t_scale_tracegen * 1e6,
            f"{n_jobs}-job heavy-tailed trace generation (wall)",
        )
    )

    # -- streaming replay: the same scale trace, memory-flat -------------
    # No JobRecord list accumulates; every statistic (and the SLO rows
    # below) comes from the live metrics registry.  Asserted in-run to
    # match the accumulated warm replay within the histogram's
    # documented quantile error bound.
    reg = metrics if metrics is not None else MetricsRegistry()
    slo = SLOMonitor(
        default=SLOTarget(deadline=_SLO_DEADLINE_S), registry=reg
    )
    t0 = time.perf_counter()
    streamed = replay(
        scale_trace,
        scale_fabric,
        plan_cache=cache,
        stream=True,
        metrics=reg,
        slo=slo,
    )
    t_stream = time.perf_counter() - t0
    _assert_stream_parity(warm, streamed)
    rows.append(
        (
            "mt_stream_events_per_sec",
            streamed.events_fired / t_stream,
            f"{streamed.events_fired} events streamed (no record list, "
            f"{t_stream * 1e3:.0f}ms wall); stats match the accumulated "
            "replay within histogram bounds (asserted)",
        )
    )
    rows.append(
        (
            "mt_p99_wait_us",
            warm.p99_queueing_delay * 1e6,
            f"p99 admission wait over {warm.n_completed} scale jobs "
            f"(streamed estimate {streamed.p99_queueing_delay * 1e6:.1f}"
            "us from the log-bucketed histogram)",
        )
    )
    for tenant, ts in sorted(warm.per_tenant().items()):
        rows.append(
            (
                f"mt_scale_{tenant}_overlap_eff",
                ts.overlap_efficiency,
                f"hidden/(hidden+exposed) reconfiguration over "
                f"{ts.n_completed} completed jobs",
            )
        )
        rows.append(
            (
                f"mt_scale_{tenant}_deadline_miss_rate",
                slo.miss_rate(tenant),
                f"jobs finishing later than "
                f"{_SLO_DEADLINE_S * 1e3:.0f}ms after arrival "
                f"(windowed p99 {slo.window_quantiles(tenant)[2] * 1e3:.2f}ms)",
            )
        )

    # -- model-trace replay: closed-loop traces from the real model stack
    # Static per-step collective traces (repro.trace) replayed through
    # the arbiter with the SWOT planner vs the strawman-ICR baseline:
    # deterministic per-model end-to-end step times with and without
    # intra-collective reconfiguration overlap, plus a co-located
    # scenario (MoE training beside dense serving on ONE shared fabric).
    t0 = time.perf_counter()
    trace_fabric = OpticalFabric(_N_NODES, 4, t_recfg=200e-6)
    model_steps = 1 if quick else 2
    for arch in ("gemma_2b", "qwen2_moe_a2_7b"):
        mt = static_trace(
            arch, kind="train", dp=2, tp=4, n_steps=model_steps
        )
        comp = overlap_comparison(
            mt, trace_fabric, size_scale=_TRACE_SIZE_SCALE
        )[mt.model]
        rows.append(
            (
                f"model_trace_{arch}_step_cct",
                comp["step_time"] * 1e6,
                f"{mt.n_events} events/step x{mt.n_steps} steps, "
                "SWOT overlap on",
            )
        )
        rows.append(
            (
                f"model_trace_{arch}_strawman_cct",
                comp["strawman_step_time"] * 1e6,
                "same trace, strawman-ICR (overlap off)",
            )
        )
        rows.append(
            (
                f"model_trace_{arch}_overlap_gain",
                comp["overlap_gain"],
                "fractional step-time reduction from overlap",
            )
        )
    colo_traces = [
        static_trace(
            "qwen2_moe_a2_7b", kind="train", dp=2, tp=4,
            n_steps=model_steps,
        ),
        static_trace(
            "gemma_2b", kind="prefill", dp=2, tp=4, n_steps=model_steps
        ),
    ]
    colo_report, colo_times = replay_trace(
        colo_traces, trace_fabric, size_scale=_TRACE_SIZE_SCALE
    )
    for arch, st in sorted(colo_times.items()):
        tstats = colo_report.per_tenant()[arch]
        rows.append(
            (
                f"model_trace_colo_{arch}_step_cct",
                st.step_time * 1e6,
                f"co-located train+serve on one fabric; "
                f"{st.n_completed}/{st.n_jobs} jobs, mean queue "
                f"{tstats.mean_queueing_delay * 1e6:.1f}us",
            )
        )
    # Per-collective-site exposed-reconfiguration fraction over the
    # co-located replay (the attribution rollup, straight from the
    # JobRecord components): exposed/(exposed+hidden), lower is better.
    site_recfg: dict[str, list[float]] = {}
    for r in colo_report.completed:
        acc = site_recfg.setdefault(r.site, [0.0, 0.0, 0])
        acc[0] += r.t_recfg_exposed
        acc[1] += r.t_recfg_hidden
        acc[2] += 1
    for site, (exposed, hidden, n_done) in sorted(site_recfg.items()):
        if exposed + hidden <= 0.0:
            continue  # site carried no reconfigurations
        slug = re.sub(r"[^0-9A-Za-z]+", "_", site).strip("_")
        rows.append(
            (
                f"model_trace_site_{slug}_exposed_frac",
                exposed / (exposed + hidden),
                f"exposed share of {(exposed + hidden) * 1e6:.1f}us "
                f"plane-mean reconfiguration over {n_done} jobs at "
                f"site {site}",
            )
        )
    rows.append(
        (
            "mt_phase_model_trace_us",
            (time.perf_counter() - t0) * 1e6,
            "model-trace extraction + overlap on/off replays (wall)",
        )
    )

    rows.append(
        (
            "multi_tenant_wall_time",
            (time.perf_counter() - t_wall) * 1e6,
            "bench runtime",
        )
    )
    return rows


if __name__ == "__main__":
    import argparse
    import contextlib
    import json

    from repro.obs import ChromeTracer, get_logger

    parser = argparse.ArgumentParser(
        description="multi-tenant arbitration sweep + runtime scale gate"
    )
    parser.add_argument(
        "--quick", action="store_true", help="single sweep cell"
    )
    parser.add_argument(
        "--jobs",
        type=int,
        default=None,
        help=f"scale-trace job count (default {_SCALE_JOBS})",
    )
    parser.add_argument(
        "--arrival",
        type=float,
        default=None,
        help=f"scale-trace mean arrival rate/s (default {_SCALE_RATE})",
    )
    parser.add_argument(
        "--trace",
        metavar="OUT.json",
        default=None,
        help="record the cold scale replay with ChromeTracer to this file",
    )
    parser.add_argument(
        "--metrics-out",
        metavar="BASE",
        default=None,
        help="export the streamed scale replay's live metrics to "
        "BASE.json (full fidelity) and BASE.prom (Prometheus text)",
    )
    args = parser.parse_args()

    log = get_logger("multi_tenant_bench")
    metrics = MetricsRegistry() if args.metrics_out else None
    # Context-managed tracer: the Chrome trace flushes even if a replay
    # assertion trips mid-run.
    with contextlib.ExitStack() as stack:
        tracer = None
        if args.trace:
            tracer = stack.enter_context(ChromeTracer(path=args.trace))
        for name, us, note in run(
            quick=args.quick,
            jobs=args.jobs,
            arrival=args.arrival,
            tracer=tracer,
            metrics=metrics,
        ):
            log.data(f"{name},{us:.1f},{note}")
    if tracer is not None:
        log.info(f"wrote {args.trace}")
    if args.metrics_out:
        with open(args.metrics_out + ".json", "w") as fh:
            json.dump(metrics.to_json(), fh)
        with open(args.metrics_out + ".prom", "w") as fh:
            fh.write(metrics.to_prometheus_text())
        log.info(
            f"wrote {args.metrics_out}.json and {args.metrics_out}.prom "
            f"({len(metrics.families())} metric families)"
        )
