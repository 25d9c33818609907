"""Tests for the live metrics substrate (`repro.obs.metrics`), the SLO
monitor layered on it (`repro.obs.slo`), and the instrumented runtime:
histogram merge algebra and quantile error bounds, exporter round-trips,
streaming-vs-accumulated replay parity, and the per-site attribution
conservation contract (DESIGN.md section 20)."""

import json
import math
import types

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.registry import get_config
from repro.core import OpticalFabric
from repro.obs.metrics import (
    DEFAULT_RESOLUTION,
    NULL_REGISTRY,
    MetricsRegistry,
    NullRegistry,
    _HistogramValue,
    main as metrics_main,
    validate_prometheus_text,
)
from repro.obs.slo import SLOMonitor, SLOTarget
from repro.obs.trace import ChromeTracer, validate_trace_file
from repro.runtime import arch_request_mix, poisson_trace, replay

# -- histogram algebra ------------------------------------------------------

_VALUES = st.lists(st.floats(1e-7, 1e6), min_size=1, max_size=200)
_ANY_VALUES = st.lists(st.floats(-10.0, 1e4), min_size=0, max_size=100)


def _hist(values, resolution=DEFAULT_RESOLUTION):
    h = _HistogramValue(resolution)
    for v in values:
        h.observe(v)
    return h


def _state(h):
    return (h._n, h._zero, dict(h._buckets), h._min, h._max)


def test_empty_histogram():
    h = _HistogramValue()
    assert h.count == 0
    assert math.isnan(h.quantile(0.5))
    assert math.isnan(h.min) and math.isnan(h.max)
    assert math.isnan(h.mean)


def test_nonpositive_values_land_in_zero_bucket():
    h = _hist([0.0, -1.0, -0.5, 2.0])
    assert h._zero == 3
    assert h.count == 4
    # Ranks 0..2 fall inside the zero region.
    assert h.quantile(0.0) == 0.0
    assert h.quantile(0.5) == 0.0
    assert h.min == -1.0 and h.max == 2.0


def test_single_value_quantile_is_exact():
    for v in (1.0, 3.7e-5, 123456.0, 2.0 ** 20):
        h = _hist([v])
        for q in (0.0, 0.5, 0.99, 1.0):
            assert h.quantile(q) == v  # clamped to the observed max


def test_resolution_validation():
    with pytest.raises(ValueError):
        _HistogramValue(0)
    with pytest.raises(ValueError):
        _hist([1.0]).quantile(1.5)


@settings(max_examples=50)
@given(_VALUES)
def test_quantile_error_bound(values):
    """quantile(q) brackets the true rank value from above, within the
    documented relative bound 2**(1/resolution) - 1."""
    h = _hist(values)
    bound = h.quantile_error
    ordered = sorted(values)
    n = len(ordered)
    for q in (0.0, 0.25, 0.5, 0.9, 0.95, 0.99, 1.0):
        true = ordered[min(n - 1, int(q * n))]
        est = h.quantile(q)
        assert true * (1 - 1e-12) <= est
        assert est <= true * (1 + bound) * (1 + 1e-12)


@settings(max_examples=30)
@given(_ANY_VALUES, _ANY_VALUES, _ANY_VALUES)
def test_merge_is_associative_and_commutative(a, b, c):
    ha, hb, hc = _hist(a), _hist(b), _hist(c)
    left = ha.merge(hb).merge(hc)
    right = ha.merge(hb.merge(hc))
    assert _state(left) == _state(right)  # integer adds: exactly equal
    assert _state(ha.merge(hb)) == _state(hb.merge(ha))
    # Merging shards equals observing centrally.
    central = _hist(a + b + c)
    assert _state(left) == _state(central)
    for q in (0.5, 0.95, 0.99):
        got, want = left.quantile(q), central.quantile(q)
        # Empty histograms (hypothesis draws a = b = c = []) have no
        # quantile: NaN on both sides.
        assert got == want or (math.isnan(got) and math.isnan(want))
    assert math.isclose(
        left.sum, central.sum, rel_tol=1e-9, abs_tol=1e-12
    )


def test_merge_rejects_resolution_mismatch():
    with pytest.raises(ValueError):
        _HistogramValue(16).merge_from(_HistogramValue(8))


def test_merge_does_not_mutate_operands():
    ha, hb = _hist([1.0, 2.0]), _hist([3.0])
    sa, sb = _state(ha), _state(hb)
    ha.merge(hb)
    assert _state(ha) == sa and _state(hb) == sb


# -- families and registry --------------------------------------------------


def test_counter_semantics():
    reg = MetricsRegistry()
    c = reg.counter("jobs_total", "jobs", ("tenant",))
    c.labels("a").inc()
    c.labels("a").inc(2.5)
    c.labels(tenant="b").inc()
    assert c.labels("a").value == 3.5
    assert c.collect() == {("a",): c.labels("a"), ("b",): c.labels("b")}
    with pytest.raises(ValueError):
        c.labels("a").inc(-1.0)
    with pytest.raises(ValueError):
        c.labels("a", "extra")
    with pytest.raises(ValueError):
        c.inc()  # labeled family has no default cell


def test_gauge_and_unlabeled_family():
    reg = MetricsRegistry()
    g = reg.gauge("depth")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value == 3.0


def test_registry_create_or_get_validates():
    reg = MetricsRegistry()
    c1 = reg.counter("x_total", "help", ("tenant",))
    assert reg.counter("x_total", "", ("tenant",)) is c1
    with pytest.raises(ValueError):
        reg.gauge("x_total")  # kind mismatch
    with pytest.raises(ValueError):
        reg.counter("x_total", "", ("other",))  # label mismatch
    reg.histogram("h_seconds", resolution=16)
    with pytest.raises(ValueError):
        reg.histogram("h_seconds", resolution=8)
    with pytest.raises(ValueError):
        reg.counter("bad name")
    with pytest.raises(ValueError):
        reg.counter("ok_total", "", ("le",))  # reserved label


def _populated_registry():
    reg = MetricsRegistry()
    c = reg.counter("rpc_total", "calls", ("tenant",))
    c.labels("a").inc(5)
    c.labels('we"ird\\t').inc(1)  # exercises label escaping
    reg.gauge("depth", "queue depth").set(7)
    h = reg.histogram("wait_seconds", "wait", ("tenant",))
    for i in range(50):
        h.labels("a").observe(1e-5 * (i + 1))
        h.labels("b").observe(0.0 if i % 7 == 0 else 2.0 ** (i % 9))
    return reg


def test_prometheus_text_round_trip_validates():
    reg = _populated_registry()
    text = reg.to_prometheus_text()
    n = validate_prometheus_text(text)
    assert n > 10
    assert "# TYPE wait_seconds histogram" in text
    assert 'le="+Inf"' in text


def test_prometheus_validator_rejects_malformed():
    with pytest.raises(ValueError):
        validate_prometheus_text("this is { not a sample\n")
    with pytest.raises(ValueError):
        validate_prometheus_text("no_type_metric 1.0\n")
    bad_cumulative = (
        "# TYPE h histogram\n"
        'h_bucket{le="1.0"} 5\n'
        'h_bucket{le="2.0"} 3\n'
        'h_bucket{le="+Inf"} 5\n'
    )
    with pytest.raises(ValueError):
        validate_prometheus_text(bad_cumulative)
    no_inf = "# TYPE h histogram\n" 'h_bucket{le="1.0"} 5\n'
    with pytest.raises(ValueError):
        validate_prometheus_text(no_inf)
    count_mismatch = (
        "# TYPE h histogram\n"
        'h_bucket{le="+Inf"} 5\n'
        "h_count 4\n"
    )
    with pytest.raises(ValueError):
        validate_prometheus_text(count_mismatch)


def test_json_round_trip_full_fidelity():
    reg = _populated_registry()
    payload = json.loads(json.dumps(reg.to_json()))
    back = MetricsRegistry.from_json(payload)
    assert back.to_json() == reg.to_json()
    assert back.to_prometheus_text() == reg.to_prometheus_text()
    h0 = reg.get("wait_seconds").aggregate()
    h1 = back.get("wait_seconds").aggregate()
    for q in (0.5, 0.95, 0.99):
        assert h0.quantile(q) == h1.quantile(q)


def test_from_json_rejects_corruption():
    good = _populated_registry().to_json()
    with pytest.raises(ValueError):
        MetricsRegistry.from_json({"metrics": [], "version": 2})
    with pytest.raises(ValueError):
        MetricsRegistry.from_json({"version": 1})
    bad_kind = json.loads(json.dumps(good))
    bad_kind["metrics"][0]["kind"] = "mystery"
    with pytest.raises(ValueError):
        MetricsRegistry.from_json(bad_kind)
    bad_counts = json.loads(json.dumps(good))
    for entry in bad_counts["metrics"]:
        if entry["kind"] == "histogram":
            entry["samples"][0]["count"] += 1  # buckets no longer sum
    with pytest.raises(ValueError):
        MetricsRegistry.from_json(bad_counts)


def test_registry_merge_from():
    a, b = _populated_registry(), _populated_registry()
    merged = MetricsRegistry()
    merged.merge_from(a)
    merged.merge_from(b)
    assert (
        merged.get("rpc_total").labels("a").value
        == 2 * a.get("rpc_total").labels("a").value
    )
    hm = merged.get("wait_seconds").aggregate()
    ha = a.get("wait_seconds").aggregate()
    assert hm.count == 2 * ha.count
    assert hm.quantile(0.95) == ha.quantile(0.95)  # same distribution


def test_cli_validate_and_merge(tmp_path, capsys):
    reg = _populated_registry()
    prom = tmp_path / "metrics.prom"
    prom.write_text(reg.to_prometheus_text())
    js = tmp_path / "metrics.json"
    js.write_text(json.dumps(reg.to_json()))
    assert metrics_main(["validate", str(prom), str(js)]) == 0
    out = tmp_path / "merged.json"
    assert metrics_main(["merge", str(out), str(js), str(js)]) == 0
    assert metrics_main(["validate", str(out)]) == 0
    merged = MetricsRegistry.from_json(json.loads(out.read_text()))
    assert (
        merged.get("rpc_total").labels("a").value
        == 2 * reg.get("rpc_total").labels("a").value
    )
    bad = tmp_path / "bad.json"
    bad.write_text('{"version": 9}')
    assert metrics_main(["validate", str(bad)]) == 1
    assert metrics_main([]) == 2
    capsys.readouterr()


def test_null_registry_is_inert():
    assert NULL_REGISTRY.enabled is False
    assert isinstance(NULL_REGISTRY, NullRegistry)
    c = NULL_REGISTRY.counter("anything", "", ("a", "b"))
    assert c.labels("x", "y") is c  # shared no-op cell
    c.inc()
    c.labels("x").observe(3.0)
    h = NULL_REGISTRY.histogram("h")
    assert math.isnan(h.quantile(0.5))
    assert h.count == 0


# -- SLO monitor ------------------------------------------------------------


def _rec(tenant, arrival, finish, rejected=False):
    return types.SimpleNamespace(
        tenant=tenant, arrival=arrival, finish=finish, rejected=rejected
    )


def test_slo_deadline_and_rejection_misses():
    mon = SLOMonitor(
        {"a": SLOTarget(deadline=1.0)}, default=SLOTarget(deadline=10.0)
    )
    assert mon.observe(_rec("a", 0.0, 0.5)) is False
    assert mon.observe(_rec("a", 0.0, 2.0)) is True  # deadline miss
    assert mon.observe(_rec("a", 0.0, 0.0, rejected=True)) is True
    assert mon.observe(_rec("b", 0.0, 5.0)) is False  # default target
    assert mon.observe(_rec("c", 0.0, 1e9)) is True  # default, missed
    assert mon.miss_rate("a") == pytest.approx(2 / 3)
    assert mon.miss_rate("unknown") == 0.0
    snap = mon.snapshot()
    assert snap["a"].n_jobs == 3 and snap["a"].n_miss == 2
    assert snap["a"].target.deadline == 1.0
    assert "a" in mon.summary()


def test_slo_target_validation():
    with pytest.raises(ValueError):
        SLOTarget(deadline=0.0)
    with pytest.raises(ValueError):
        SLOMonitor(window=0.0)
    with pytest.raises(ValueError):
        SLOMonitor(max_windows=0)


def test_slo_window_semantics():
    mon = SLOMonitor(window=10.0, max_windows=2)
    # Window 0: fast responses; window 5: slow ones.
    for i in range(10):
        mon.observe(_rec("a", float(i) * 0.1, float(i) * 0.1 + 0.001))
    for i in range(10):
        mon.observe(_rec("a", 50.0, 50.0 + 4.0 + i * 0.01))
    last = mon.window_quantiles("a", last=1)
    assert last[1] > 1.0  # p95 of the latest window is the slow batch
    both = mon.window_histogram("a")
    assert both.count == 20  # both windows retained (max_windows=2)
    # A third window evicts the oldest but totals survive.
    mon.observe(_rec("a", 100.0, 100.5))
    assert mon.window_histogram("a").count == 11
    assert mon.snapshot()["a"].n_jobs == 21
    with pytest.raises(ValueError):
        mon.window_quantiles("a", last=0)
    assert mon.window_histogram("ghost").count == 0


def test_slo_windowed_quantiles_match_merged_histogram():
    mon = SLOMonitor(window=1.0, max_windows=8)
    responses = [0.01 * (i + 1) for i in range(40)]
    for i, r in enumerate(responses):
        mon.observe(_rec("a", float(i % 5), float(i % 5) + r))
    # What the monitor actually measured, rounding included.
    direct = _hist(
        [(float(i % 5) + r) - float(i % 5)
         for i, r in enumerate(responses)]
    )
    merged = mon.window_histogram("a")
    assert _state(merged) == _state(direct)
    for q in (0.5, 0.95, 0.99):
        assert merged.quantile(q) == direct.quantile(q)


def test_slo_publishes_to_registry():
    reg = MetricsRegistry()
    mon = SLOMonitor(
        {"a": SLOTarget(deadline=0.5)}, registry=reg
    )
    mon.observe(_rec("a", 0.0, 1.0))
    mon.observe(_rec("a", 0.0, 0.1))
    assert reg.get("slo_jobs_total").labels("a").value == 2
    assert reg.get("slo_deadline_miss_total").labels("a").value == 1
    assert reg.get("slo_miss_rate").labels("a").value == 0.5


# -- instrumented runtime ---------------------------------------------------


def _mixes(n_tenants=2):
    mix = arch_request_mix(get_config("qwen3_4b"), n_nodes=8)
    return [(f"t{i}", mix) for i in range(n_tenants)]


@pytest.fixture(scope="module")
def runtime_trace():
    return poisson_trace(_mixes(2), rate=30.0, horizon=0.25, seed=7)


@pytest.fixture(scope="module")
def fabric():
    return OpticalFabric(8, 4, t_recfg=200e-6)


@pytest.fixture(scope="module")
def metered_report(runtime_trace, fabric):
    return replay(
        runtime_trace,
        fabric,
        metrics=MetricsRegistry(),
        solo_refs=False,
    )


def _record_key(report):
    return [
        (r.job_id, r.tag, r.start, r.finish, r.cct, r.queueing_delay)
        for r in report.records
    ]


def test_metrics_do_not_perturb_the_timeline(
    runtime_trace, fabric, metered_report
):
    bare = replay(runtime_trace, fabric, solo_refs=False)
    assert _record_key(bare) == _record_key(metered_report)
    assert bare.makespan == metered_report.makespan
    assert bare.stats == metered_report.stats


def test_per_job_attribution_is_conserved_bitwise(metered_report):
    done = metered_report.completed
    assert done
    saw_recfg = False
    for r in done:
        comp = (
            (r.t_xmit + r.t_bypass) + r.t_recfg_exposed
        ) + r.t_recfg_hidden
        assert comp + r.t_idle == r.cct  # exact, not approx
        saw_recfg = saw_recfg or (
            r.t_recfg_exposed + r.t_recfg_hidden > 0.0
        )
        assert r.overlap_efficiency is not None
        assert 0.0 <= r.overlap_efficiency <= 1.0
    assert saw_recfg  # the trace must actually exercise reconfigurations


def test_attribution_parity_optimize_on_off(runtime_trace, fabric):
    slow = replay(
        runtime_trace, fabric, optimize=False, solo_refs=False
    )
    fast = replay(
        runtime_trace, fabric, optimize=True, solo_refs=False
    )
    for a, b in zip(slow.records, fast.records):
        assert (a.t_xmit, a.t_bypass, a.t_recfg_exposed,
                a.t_recfg_hidden, a.t_idle) == (
            b.t_xmit, b.t_bypass, b.t_recfg_exposed,
            b.t_recfg_hidden, b.t_idle,
        )


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_attribution_conserved_on_every_backend(
    runtime_trace, fabric, backend
):
    from repro.core.ir.backends import get_backend

    try:
        get_backend(backend)
    except Exception as exc:  # backend not importable in this image
        pytest.skip(f"{backend} unavailable: {exc}")
    report = replay(
        runtime_trace, fabric, backend=backend, solo_refs=False
    )
    for r in report.completed:
        comp = (
            (r.t_xmit + r.t_bypass) + r.t_recfg_exposed
        ) + r.t_recfg_hidden
        assert comp + r.t_idle == r.cct


def test_registry_counts_match_records(metered_report):
    reg = metered_report.metrics
    recs = metered_report.records
    jobs = reg.get("fabric_jobs_total")
    assert sum(c.value for c in jobs.collect().values()) == len(recs)
    done = metered_report.completed
    completed = reg.get("fabric_jobs_completed_total")
    assert sum(
        c.value for c in completed.collect().values()
    ) == len(done)
    wait = reg.get("fabric_queue_wait_seconds").aggregate()
    started = [r for r in recs if r.start is not None]
    assert wait.count == len(started)
    true_mean = sum(r.queueing_delay for r in started) / len(started)
    assert wait.mean == pytest.approx(true_mean, rel=1e-9)
    events = reg.get("sim_events_total")
    assert events.value == metered_report.events_fired


def test_site_rollups_sum_to_cct(metered_report):
    reg = metered_report.metrics
    per_site = {}
    for r in metered_report.completed:
        key = (r.tenant, r.site)
        acc = per_site.setdefault(key, [0.0, 0.0])
        acc[0] += r.cct
        acc[1] += 1
    parts = [
        reg.get(f"fabric_site_{p}_seconds_total")
        for p in ("xmit", "bypass", "recfg_exposed", "recfg_hidden",
                  "idle")
    ]
    cct_fam = reg.get("fabric_site_cct_seconds_total")
    n_fam = reg.get("fabric_site_jobs_total")
    assert set(cct_fam.collect()) == set(per_site)
    for key, (cct_sum, n) in per_site.items():
        assert n_fam.labels(*key).value == n
        assert cct_fam.labels(*key).value == pytest.approx(
            cct_sum, rel=1e-9
        )
        total = sum(p.labels(*key).value for p in parts)
        assert total == pytest.approx(cct_sum, rel=1e-9)


def test_plan_cache_metrics_sync(metered_report):
    reg = metered_report.metrics
    cache = metered_report.cache
    assert cache is not None and cache.hits > 0
    assert reg.get("fabric_plan_cache_hits_total").value == cache.hits
    assert (
        reg.get("fabric_plan_cache_misses_total").value == cache.misses
    )
    assert reg.get(
        "fabric_plan_wall_seconds_total"
    ).value == pytest.approx(cache.plan_wall_s, rel=1e-9)


def test_streaming_matches_accumulated(
    runtime_trace, fabric, metered_report
):
    """A streamed replay (no record list) serves the same statistics
    from the registry, within the histogram's documented error bound."""
    sunk = []
    streamed = replay(
        runtime_trace,
        fabric,
        stream=True,
        slo=SLOMonitor(default=SLOTarget(deadline=0.5)),
        record_sink=sunk.append,
    )
    acc = metered_report
    assert streamed.records == []  # memory-flat: nothing accumulated
    assert len(sunk) == acc.n_jobs  # every record reached the sink
    assert streamed.n_jobs == acc.n_jobs
    assert streamed.n_completed == acc.n_completed
    assert streamed.mean_cct == pytest.approx(acc.mean_cct, rel=1e-9)
    assert streamed.mean_queueing_delay == pytest.approx(
        acc.mean_queueing_delay, rel=1e-9
    )
    err = streamed.metrics.get(
        "fabric_queue_wait_seconds"
    ).aggregate().quantile_error
    for q_attr in ("p95_queueing_delay", "p99_queueing_delay"):
        true = getattr(acc, q_attr)
        est = getattr(streamed, q_attr)
        assert true * (1 - 1e-9) <= est <= true * (1 + err) * (1 + 1e-9)
    acc_tenants = acc.per_tenant()
    str_tenants = streamed.per_tenant()
    assert set(acc_tenants) == set(str_tenants)
    for tenant, a in acc_tenants.items():
        s = str_tenants[tenant]
        assert s.n_jobs == a.n_jobs
        assert s.n_completed == a.n_completed
        assert s.n_rejected == a.n_rejected
        assert s.total_bytes == pytest.approx(a.total_bytes, rel=1e-9)
        assert s.mean_cct == pytest.approx(a.mean_cct, rel=1e-9)
        assert s.mean_queueing_delay == pytest.approx(
            a.mean_queueing_delay, rel=1e-9
        )
        assert (
            a.p95_queueing_delay * (1 - 1e-9)
            <= s.p95_queueing_delay
            <= a.p95_queueing_delay * (1 + err) * (1 + 1e-9)
        )
        assert s.overlap_efficiency == pytest.approx(
            a.overlap_efficiency, rel=1e-9
        )
    assert streamed.slo is not None
    assert streamed.slo.tenants() == ("t0", "t1")
    assert "t0" in streamed.summary()


def test_site_id_threads_from_trace_events():
    from repro.trace.records import CollectiveTrace, TraceEvent
    from repro.trace.replay import replay_trace, trace_to_jobs

    trace = CollectiveTrace(
        model="toy",
        source="static",
        events=(
            TraceEvent(op="ring_allreduce", payload_bytes=1e5,
                       participants=8, tag="grads"),
            TraceEvent(op="all_gather", payload_bytes=1e5,
                       participants=8, deps=(0,),
                       site_id="custom/site"),
        ),
        n_steps=2,
    )
    fab = OpticalFabric(8, 4, t_recfg=200e-6)
    jobs = trace_to_jobs(trace, fab)
    sites = sorted({j.site_id for j in jobs})
    assert sites == ["custom/site", "toy/grads"]
    assert all(j.tenant == "toy" for j in jobs)
    report, _ = replay_trace(
        trace, fab, overlap=True, metrics=MetricsRegistry()
    )
    rec_sites = {r.site for r in report.completed}
    assert rec_sites == {"custom/site", "toy/grads"}
    site_fam = report.metrics.get("fabric_site_jobs_total")
    assert {k[1] for k in site_fam.collect()} == rec_sites


# -- ChromeTracer context manager -------------------------------------------


def test_chrome_tracer_context_manager_writes(tmp_path):
    path = tmp_path / "trace.json"
    with ChromeTracer(path=str(path)) as tracer:
        tracer.span("work", 0.0, 1.0, tid=0)
    validate_trace_file(str(path))


def test_chrome_tracer_flushes_on_exception(tmp_path):
    path = tmp_path / "crash.json"
    with pytest.raises(RuntimeError, match="boom"):
        with ChromeTracer(path=str(path)) as tracer:
            tracer.span("partial", 0.0, 0.5, tid=1)
            raise RuntimeError("boom")
    validate_trace_file(str(path))  # partial trace is still valid
    payload = json.loads(path.read_text())
    names = [e["name"] for e in payload["traceEvents"]]
    assert "partial" in names


def test_chrome_tracer_without_path_is_unmanaged(tmp_path):
    with ChromeTracer() as tracer:
        tracer.instant("tick", 0.0)
    assert tracer.path is None  # nothing written, nothing raised
