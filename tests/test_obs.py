"""Tests for the observability layer (`repro.obs`).

Three contracts:

* **Attribution conservation** -- the per-(instance, step, plane)
  component arrays from ``batch_evaluate(..., attribution=True)`` must
  sum *bitwise* to the evaluator's CCT on every timing backend, in both
  dependency modes, and on bypass-carrying batches; the object-walk
  oracle (``attribute`` over an executed ``Schedule``) must agree.
* **Trace schema** -- ``ChromeTracer`` output must satisfy the
  trace-event validator the CI smoke job uses, the runtime's
  instrumentation must emit the documented lifecycle events, and a
  traced replay must be bit-identical to an untraced one.
* **Logger knob** -- ``REPRO_LOG`` renders/suppresses the narrative
  channel without ever touching the ``data`` channel.
"""

import json

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    BatchInstance,
    CollectiveRequest,
    OpticalFabric,
    batch_evaluate,
    get_pattern,
    prestage_for,
    strawman_instance,
    swot_greedy_grid,
)
from repro.core.ir import BackendUnavailable, get_backend
from repro.core.schedule import DependencyMode
from repro.obs import (
    NULL_TRACER,
    ChromeTracer,
    NullTracer,
    ObsLogger,
    attribute,
    trace_schedule,
    validate_trace,
    validate_trace_file,
)
from repro.obs.log import ENV_LOG
from repro.runtime import FabricArbiter, SimEngine, replay
from repro.runtime.workload import JobSpec


def _available_backends():
    names = []
    for name in ("numpy", "jax"):
        try:
            get_backend(name)
        except BackendUnavailable:
            continue
        names.append(name)
    return names


_BACKENDS = _available_backends()


def _mixed_instances():
    """A shape-heterogeneous batch: greedy plans + strawman lockstep."""
    instances = []
    for alg, n, planes, t_recfg in (
        ("rabenseifner_allreduce", 8, 4, 200e-6),
        ("pairwise_alltoall", 8, 4, 3.2e-3),
        ("pairwise_alltoall", 6, 2, 0.0),
        ("all_gather", 8, 3, 50e-6),
    ):
        pattern = get_pattern(alg, n, 8e6)
        fabric = prestage_for(
            OpticalFabric(n, planes, t_recfg=t_recfg), pattern
        )
        instances.append(strawman_instance(fabric, pattern))
    plans = swot_greedy_grid(
        [(inst.fabric, inst.pattern) for inst in instances]
    )
    instances += [
        BatchInstance(p.fabric, p.pattern, p.decisions) for p in plans
    ]
    return instances


def _bypass_instances():
    """Plans whose decisions carry relay routes (high-t_recfg regime)."""
    pattern = get_pattern("pairwise_alltoall", 8, 8e6)
    cells = [
        (
            OpticalFabric(8, 4, t_recfg=t).prestaged(
                pattern.steps[0].config
            ),
            pattern,
        )
        for t in (8e-4, 3.2e-3)
    ]
    plans = swot_greedy_grid(cells, bypass_depth=2)
    instances = [
        BatchInstance(p.fabric, p.pattern, p.decisions) for p in plans
    ]
    assert any(
        inst.decisions.bypass
        and any(routes for routes in inst.decisions.bypass)
        for inst in instances
    ), "bypass batch carries no relays; the fixture regressed"
    return instances


def _assert_conserved(result):
    att = result.attribution
    assert att is not None
    total = np.where(att.plane_mask, att.plane_total, 0.0)
    want = np.where(att.plane_mask, result.cct[..., None], 0.0)
    assert np.array_equal(total, want), (
        "components + idle do not sum bitwise to CCT"
    )
    # Masked steps/planes carry no time.
    step_live = att.step_mask[..., :, None] & att.plane_mask[..., None, :]
    for comp in (
        att.t_xmit, att.t_bypass, att.t_recfg_wait, att.t_recfg_hidden
    ):
        assert not np.any(np.where(step_live, 0.0, comp)), (
            "attribution leaked time into masked cells"
        )


class TestConservation:
    @pytest.mark.parametrize("backend", _BACKENDS)
    @pytest.mark.parametrize(
        "mode", [DependencyMode.CHAIN, DependencyMode.INDEPENDENT]
    )
    def test_bitwise_conservation(self, backend, mode):
        instances = _mixed_instances()
        if mode is DependencyMode.INDEPENDENT:
            cells = [(i.fabric, i.pattern) for i in instances[:4]]
            plans = swot_greedy_grid(cells, mode=mode)
            instances = [
                BatchInstance(p.fabric, p.pattern, p.decisions)
                for p in plans
            ]
        result = batch_evaluate(
            instances, backend=backend, attribution=True
        )
        _assert_conserved(result)
        if mode is DependencyMode.INDEPENDENT:
            # No barrier to hide behind: nothing may be attributed as
            # overlapped reconfiguration.
            assert not np.any(result.attribution.t_recfg_hidden)

    @pytest.mark.parametrize("backend", _BACKENDS)
    def test_bypass_batches_conserve(self, backend):
        result = batch_evaluate(
            _bypass_instances(), backend=backend, attribution=True
        )
        _assert_conserved(result)
        assert np.any(result.attribution.t_bypass > 0.0), (
            "relay time was not attributed to the bypass component"
        )

    @pytest.mark.parametrize("backend", _BACKENDS)
    def test_attribution_flag_does_not_perturb_cct(self, backend):
        instances = _mixed_instances()
        base = batch_evaluate(instances, backend=backend)
        att = batch_evaluate(instances, backend=backend, attribution=True)
        assert base.attribution is None
        assert np.array_equal(base.cct, att.cct)
        assert np.array_equal(
            base.n_reconfigurations, att.n_reconfigurations
        )

    def test_empty_batch(self):
        result = batch_evaluate([], attribution=True)
        assert result.attribution is not None
        assert result.attribution.cct.shape == (0,)
        assert result.attribution.overlap_efficiency.shape == (0,)

    def test_backends_agree_on_components(self):
        if len(_BACKENDS) < 2:
            pytest.skip("only one backend available")
        instances = _mixed_instances()
        results = {
            b: batch_evaluate(instances, backend=b, attribution=True)
            for b in _BACKENDS
        }
        ref = results["numpy"].attribution
        for name, result in results.items():
            att = result.attribution
            for field in ("t_xmit", "t_recfg_wait", "t_recfg_hidden"):
                err = float(
                    np.max(
                        np.abs(getattr(att, field) - getattr(ref, field))
                    )
                )
                assert err <= 1e-9, (
                    f"{name}.{field} diverges from numpy by {err}"
                )


@st.composite
def _rand_instances(draw):
    alg = draw(
        st.sampled_from(
            ["rabenseifner_allreduce", "pairwise_alltoall", "all_gather"]
        )
    )
    # Recursive-doubling algorithms need power-of-two node counts.
    if alg == "pairwise_alltoall":
        n = draw(st.integers(min_value=2, max_value=10))
    else:
        n = draw(st.sampled_from([2, 4, 8]))
    size = draw(st.floats(min_value=1e5, max_value=2e8))
    planes = draw(st.integers(min_value=1, max_value=4))
    t_recfg = draw(st.sampled_from([0.0, 50e-6, 200e-6, 3.2e-3]))
    prestaged = draw(st.booleans())
    mode = draw(
        st.sampled_from(
            [DependencyMode.CHAIN, DependencyMode.INDEPENDENT]
        )
    )
    return alg, n, size, planes, t_recfg, prestaged, mode


class TestOracleParity:
    @settings(max_examples=25, deadline=None)
    @given(_rand_instances())
    def test_object_walk_matches_batched(self, inst):
        alg, n, size, planes, t_recfg, prestaged, mode = inst
        pattern = get_pattern(alg, n, size)
        fabric = OpticalFabric(n, planes, t_recfg=t_recfg)
        if prestaged:
            fabric = prestage_for(fabric, pattern)
        plan = swot_greedy_grid([(fabric, pattern)], mode=mode)[0]
        result = batch_evaluate(
            [BatchInstance(plan.fabric, plan.pattern, plan.decisions)],
            attribution=True,
        )
        _assert_conserved(result)
        oracle = attribute(plan.schedule())
        att = result.attribution
        assert abs(float(oracle.cct) - float(result.cct[0])) <= 1e-9
        for field in (
            "exposed_recfg", "hidden_recfg", "overlap_efficiency"
        ):
            got = float(getattr(att, field)[0])
            want = float(getattr(oracle, field))
            assert abs(got - want) <= 1e-9, (
                f"{field}: batched {got} vs object walk {want}"
            )


class TestSemantics:
    def test_zero_recfg_time_is_vacuously_efficient(self):
        pattern = get_pattern("pairwise_alltoall", 8, 8e6)
        fabric = prestage_for(OpticalFabric(8, 4, t_recfg=0.0), pattern)
        result = batch_evaluate(
            [strawman_instance(fabric, pattern)], attribution=True
        )
        att = result.attribution
        assert not np.any(att.t_recfg_wait)
        assert not np.any(att.t_recfg_hidden)
        assert float(att.overlap_efficiency[0]) == 1.0

    def test_single_plane_strawman_hides_nothing(self):
        # One plane, CHAIN mode: every reconfiguration starts exactly at
        # the step barrier, so its full duration is exposed.
        pattern = get_pattern("pairwise_alltoall", 8, 8e6)
        fabric = prestage_for(
            OpticalFabric(8, 1, t_recfg=200e-6), pattern
        )
        result = batch_evaluate(
            [strawman_instance(fabric, pattern)], attribution=True
        )
        att = result.attribution
        # The wait is fl(free + t) - free per reconfiguration, so the
        # efficiency can sit an ulp off exact zero.
        assert float(att.overlap_efficiency[0]) == pytest.approx(
            0.0, abs=1e-9
        )
        assert float(att.exposed_recfg[0]) == pytest.approx(
            int(result.n_reconfigurations[0]) * 200e-6
        )


class TestTracer:
    def test_null_tracer_is_disabled_noop(self):
        assert NULL_TRACER.enabled is False
        assert isinstance(NULL_TRACER, NullTracer)
        NULL_TRACER.span("x", 0.0, 1.0)
        NULL_TRACER.instant("x", 0.0)
        NULL_TRACER.counter("x", 0.0, 1.0)

    def test_chrome_tracer_payload_validates(self, tmp_path):
        tracer = ChromeTracer()
        tracer.span("xmit s0", 0.0, 1e-3, tid=0, volume=8e6)
        tracer.instant("job_arrival", 5e-4, job=0)
        tracer.counter("queue_depth", 5e-4, 2)
        payload = tracer.to_json()
        validate_trace(payload)
        names = {
            ev["args"]["name"]
            for ev in payload["traceEvents"]
            if ev["ph"] == "M" and ev["name"] == "thread_name"
        }
        assert {"plane 0", "jobs"} <= names
        path = tmp_path / "trace.json"
        tracer.write(str(path))
        validate_trace_file(str(path))
        # Timestamps are microseconds.
        span = next(
            ev for ev in payload["traceEvents"] if ev["ph"] == "X"
        )
        assert span["ts"] == 0.0 and span["dur"] == pytest.approx(1e3)

    @pytest.mark.parametrize(
        "corrupt",
        [
            lambda p: p.pop("traceEvents"),
            lambda p: p["traceEvents"].append({"ph": "Q", "name": "x"}),
            lambda p: p["traceEvents"].append(
                {"ph": "X", "name": "x", "ts": 0, "pid": 1, "tid": 0}
            ),
            lambda p: p["traceEvents"].append(
                {
                    "ph": "i", "name": "x", "ts": -1.0, "pid": 1,
                    "tid": 0,
                }
            ),
            lambda p: p["traceEvents"].append(
                {
                    "ph": "M", "name": "process_name", "pid": 1,
                    "args": {"name": "dup"},
                }
            ),
            lambda p: p["traceEvents"].append(
                {"ph": "C", "name": "x", "ts": 0, "pid": 1, "args": {}}
            ),
        ],
        ids=[
            "no_events", "unknown_phase", "missing_dur", "negative_ts",
            "duplicate_process", "counter_without_value",
        ],
    )
    def test_validator_rejects_corruptions(self, corrupt):
        tracer = ChromeTracer()
        tracer.span("x", 0.0, 1.0, tid=0)
        payload = tracer.to_json()
        corrupt(payload)
        with pytest.raises(ValueError):
            validate_trace(payload)

    def test_trace_schedule_emits_one_span_per_activity(self):
        pattern = get_pattern("pairwise_alltoall", 8, 8e6)
        fabric = prestage_for(
            OpticalFabric(8, 4, t_recfg=200e-6), pattern
        )
        plan = swot_greedy_grid([(fabric, pattern)])[0]
        schedule = plan.schedule()
        tracer = ChromeTracer()
        trace_schedule(schedule, tracer)
        assert len(tracer.events) == len(schedule.activities)
        validate_trace(tracer.to_json())

    def test_arbiter_emits_lifecycle_events(self):
        tracer = ChromeTracer()
        engine = SimEngine(tracer=tracer)
        fabric = OpticalFabric(8, 4, t_recfg=200e-6)
        arbiter = FabricArbiter(engine, fabric, tracer=tracer)
        req = CollectiveRequest("pairwise_alltoall", 8, 8e6, "job_a")
        record = arbiter.run_collective(req)
        assert record.finish is not None
        validate_trace(tracer.to_json())
        instants = {
            ev["name"] for ev in tracer.events if ev["ph"] == "i"
        }
        assert {"job_arrival", "lease_grant", "job_complete"} <= instants
        span_names = {
            ev["name"] for ev in tracer.events if ev["ph"] == "X"
        }
        assert any(n.startswith("reconfig->") for n in span_names)
        assert any(n.startswith("job_a") for n in span_names)
        counters = {
            ev["name"] for ev in tracer.events if ev["ph"] == "C"
        }
        assert {
            "queue_depth", "free_planes", "running_jobs", "sim_events"
        } <= counters
        # Span wall coverage: total transmit+reconfig span time on the
        # plane rows must equal the plane_busy statistic.
        span_total = sum(
            ev["dur"] / 1e6
            for ev in tracer.events
            if ev["ph"] == "X"
        )
        busy_total = sum(arbiter.stats.plane_busy.values())
        assert span_total == pytest.approx(busy_total)

    def test_backpressure_reject_traced(self):
        tracer = ChromeTracer()
        engine = SimEngine()
        fabric = OpticalFabric(8, 2, t_recfg=200e-6)
        arbiter = FabricArbiter(
            engine, fabric, max_queue_depth=0, tracer=tracer
        )
        req = CollectiveRequest("pairwise_alltoall", 8, 8e6, "job_a")
        # With queue depth 0 and an occupied fabric the second submit
        # must be rejected (the first is granted immediately).
        arbiter.submit(req)
        rejected = arbiter.submit(req)
        assert rejected.rejected
        names = {ev["name"] for ev in tracer.events if ev["ph"] == "i"}
        assert "backpressure_reject" in names

    def test_traced_replay_is_bit_identical(self):
        fabric = OpticalFabric(8, 4, t_recfg=200e-6)
        reqs = [
            CollectiveRequest("pairwise_alltoall", 8, 4e6, "a"),
            CollectiveRequest("rabenseifner_allreduce", 8, 8e6, "b"),
            CollectiveRequest("all_gather", 8, 2e6, "c"),
        ]
        trace = [
            JobSpec(arrival=i * 2e-4, request=r)
            for i, r in enumerate(reqs * 2)
        ]
        plain = replay(trace, fabric)
        traced = replay(trace, fabric, tracer=ChromeTracer())
        assert plain.makespan == traced.makespan
        assert plain.events_fired == traced.events_fired
        assert [r.finish for r in plain.records] == [
            r.finish for r in traced.records
        ]


class TestLogger:
    def _logger(self, stream):
        return ObsLogger("t", stream=stream)

    def test_default_mode_renders_info_not_debug(
        self, monkeypatch, capsys
    ):
        monkeypatch.delenv(ENV_LOG, raising=False)
        log = ObsLogger("t")
        log.info("hello", n=3)
        log.debug("invisible")
        out = capsys.readouterr().out
        assert "hello n=3" in out
        assert "invisible" not in out

    def test_quiet_mode_keeps_warnings_and_data(
        self, monkeypatch, capsys
    ):
        monkeypatch.setenv(ENV_LOG, "quiet")
        log = ObsLogger("t")
        log.info("narrative")
        log.warning("problem")
        log.data("row,1,2")
        captured = capsys.readouterr()
        assert "narrative" not in captured.out
        assert "row,1,2" in captured.out
        assert "problem" in captured.err

    def test_json_mode_emits_parseable_records(
        self, monkeypatch, capsys
    ):
        monkeypatch.setenv(ENV_LOG, "json")
        log = ObsLogger("t")
        log.info("msg", key="value")
        record = json.loads(capsys.readouterr().out)
        assert record == {
            "level": "info", "logger": "t", "msg": "msg", "key": "value"
        }

    def test_debug_mode_unlocks_debug(self, monkeypatch, capsys):
        monkeypatch.setenv(ENV_LOG, "debug")
        log = ObsLogger("t")
        log.debug("visible")
        assert "visible" in capsys.readouterr().out
