"""`correct` on the CPU at small sizes: sound runs pass; the control (the
timed path in float32) and each fault planted under the timed path fail.

The runs skip the harness's look for a chip and drive everything else of
a run: set-up, the window, the trace's absence, the check."""

import dataclasses

import harness
import numpy as np
import pytest


def grid_correct(cell) -> dict:
    return harness.run_cell(cell, seconds=0.3)


@pytest.mark.parametrize("planner", ["fused", "step"])
def test_sound_grid_runs_are_correct(planner):
    r = grid_correct(harness.small_grid_cell(planner))
    assert r["correct"], r["compared"]
    assert r["failed"] == 0 and r["attempted"] >= 16
    assert r["compared"]["cct_excess_pos_mean"]["value"] == 0.0
    assert list(r["compared"])[-1] == "timing_gap"
    assert r["metrics"]["grid_cells_per_s"]["value"] > 0
    assert r["metrics"]["setup_s"]["unit"] == "s"


def test_float32_control_fails_the_grid_check():
    import control

    with control.precision("float32"):
        r = grid_correct(harness.small_grid_cell("fused"))
    assert not r["correct"]
    failed = {k for k, c in r["compared"].items() if c["value"] > c["limit"]}
    assert failed & {"volume_gap", "cct_excess_pos_mean", "timing_gap"}


def test_answer_altered_where_produced_fails(monkeypatch):
    """The timing scan's CCT of one cell is off by one part in 1e6."""
    from repro.core.ir import get_backend

    backend = get_backend("jax")
    derive = backend.derive_timing

    def off(packed, attribution=False):
        out = derive(packed, attribution=attribution)
        cct = np.array(out.cct)
        cct[0] *= 1 + 1e-6
        return dataclasses.replace(out, cct=cct)

    monkeypatch.setattr(backend, "derive_timing", off)
    r = grid_correct(harness.small_grid_cell("fused"))
    assert not r["correct"]
    assert r["compared"]["timing_gap"]["value"] > 1e-7


def test_decision_altered_where_produced_fails(monkeypatch):
    """One cell in 16 of every request puts every step on plane 0; the
    window holds more cells than the committed sample checks."""
    from repro.core import greedy

    decide = greedy._chain_grid_decisions

    def altered(st, horizon, planner="step"):
        out = decide(st, horizon, planner)
        for i in range(0, len(out), 16):
            splits = tuple({0: sum(s.values())} for s in out[i].splits)
            out[i] = dataclasses.replace(out[i], splits=splits)
        return out

    monkeypatch.setattr(greedy, "_chain_grid_decisions", altered)
    cell = harness.small_grid_cell("fused")
    cell.traffic.update(n_sizes=8, n_delays=8)
    assert cell.traffic["check_cells"] == 256  # the committed sample
    r = harness.run_cell(cell, seconds=2.0)
    assert r["attempted"] > 256
    assert not r["correct"]
    c = r["compared"]["cct_excess_pos_mean"]
    assert c["value"] > c["limit"]


def test_half_the_batch_left_out_fails(monkeypatch):
    from repro.core import api

    plan = api.plan

    def half(request):
        out = plan(request)
        n = len(out.ccts) // 2
        return dataclasses.replace(
            out, ccts=out.ccts[:n], grid=out.grid[:n],
            methods=out.methods[:n],
        )

    monkeypatch.setattr(api, "plan", half)
    r = grid_correct(harness.small_grid_cell("fused"))
    assert not r["correct"]
    assert r["failed"] > 0


def test_scan_step_returning_its_state_unchanged_fails(monkeypatch):
    from repro.core.ir import fused

    step = fused._chain_step

    def stuck(horizon, with_bypass, tab, carry, xs):
        _, ys = step(horizon, with_bypass, tab, carry, xs)
        return carry, ys

    monkeypatch.setattr(fused, "_chain_step", stuck)
    monkeypatch.setattr(fused, "_SCAN_CACHE", {})
    r = grid_correct(harness.small_grid_cell("fused"))
    assert not r["correct"]


# -- the fleet replay --------------------------------------------------------
def replay_correct(cell=None, seconds=2.0) -> dict:
    return harness.run_cell(cell or harness.small_replay_cell(), seconds)


def test_sound_replay_is_correct():
    r = replay_correct()
    assert r["correct"], r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["metrics"]["replay_jobs_per_s"]["value"] > 0
    assert list(r["compared"]) == [
        "jobs_unfinished", "timeline_violation", "rescoring_gap",
    ]


def test_rescoring_altered_where_produced_fails(monkeypatch):
    import repro.runtime.arbiter as arb

    evaluate = arb.batch_evaluate

    def off(instances, plane_ready=None, backend=None, **kw):
        out = evaluate(instances, plane_ready=plane_ready, backend=backend, **kw)
        return dataclasses.replace(out, cct=np.asarray(out.cct) * (1 + 1e-6))

    monkeypatch.setattr(arb, "batch_evaluate", off)
    r = replay_correct()
    assert not r["correct"]
    assert r["compared"]["rescoring_gap"]["value"] > 1e-7


def test_half_the_jobs_left_out_fails(monkeypatch):
    """Every other submitted job is acknowledged and never run."""
    from repro.runtime import FabricArbiter
    from repro.runtime.arbiter import JobRecord

    submit = FabricArbiter.submit
    seen = [0]

    def drop_half(self, req, *a, tenant="", **kw):
        seen[0] += 1
        if seen[0] % 2:
            return JobRecord(
                job_id=next(self._ids), tag=req.tag, algorithm=req.algorithm,
                n_nodes=req.n_nodes, size=req.size, priority=0,
                arrival=self.engine.now, tenant=tenant,
            )
        return submit(self, req, *a, tenant=tenant, **kw)

    monkeypatch.setattr(FabricArbiter, "submit", drop_half)
    r = replay_correct()
    assert not r["correct"]
    assert r["compared"]["jobs_unfinished"]["value"] > 0


def test_plan_altered_where_produced_fails(monkeypatch):
    from repro.core.schedule import Kind
    from repro.runtime import FabricArbiter

    build = FabricArbiter._build_plan

    def shortened(self, job, plane_ready):
        cached = build(self, job, plane_ready)
        acts = list(cached.schedule.activities)
        i = next(k for k, a in enumerate(acts) if a.kind is Kind.XMIT)
        a = acts[i]
        acts[i] = dataclasses.replace(a, end=a.start + 0.9 * (a.end - a.start))
        schedule = dataclasses.replace(cached.schedule, activities=tuple(acts))
        return type(cached)(schedule, cached.boundaries_rel)

    monkeypatch.setattr(FabricArbiter, "_build_plan", shortened)
    r = replay_correct()
    assert not r["correct"]
    assert r["compared"]["timeline_violation"]["value"] > 1e-3


def test_plane_granted_twice_fails(monkeypatch):
    """A granted lease's planes stay in the free pool, so another job can
    be granted them while the first still holds them."""
    from repro.runtime import FabricArbiter

    grant = FabricArbiter._grant

    def leaky(self, job, planes, deferred=None):
        grant(self, job, planes, deferred)
        self._free.update(planes)

    monkeypatch.setattr(FabricArbiter, "_grant", leaky)
    r = replay_correct()
    assert not r["correct"]
    assert r["compared"]["timeline_violation"]["value"] > 1e-3


def test_configs_read_across_namespaces_fails(monkeypatch):
    """A plan counts a plane's installed config as its own whichever
    collective installed it (config ids only mean something within one
    algorithm and node count), so it skips a reconfiguration it needs."""
    from repro.runtime import FabricArbiter

    def any_namespace(self, key, planes):
        return tuple(
            None if (state := self._plane_state[p]) is None else state[1]
            for p in planes
        )

    monkeypatch.setattr(FabricArbiter, "_init_configs", any_namespace)
    r = replay_correct()
    assert not r["correct"]
    assert r["compared"]["timeline_violation"]["value"] >= 1.0
