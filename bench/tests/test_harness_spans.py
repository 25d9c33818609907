"""The host spans of a traced run (`host_spans`): on synthetic records,
their count and seconds per name inside the window, with the device's
numbers left as they were; and in a traced run of the grid harness on the
CPU, the readers of the planner's ``repro.*`` spans find their numbers on
the planner they time and nothing on the other, and the spans are flat
and lie inside the harness's requests."""

import types

import harness
import host_spans
import pytest
import test_harness_trace
import trace_reduce

FUSED_CELL = "a2a128_4p.sweep1024"  # the fused planner's cell
STEP_CELL = "a2a128_4p.sweep128"  # the per-step planner's cell
NEW = {
    "grid_state_ms", "step_loop_ms", "fused_call_ms", "grid_materialize_ms",
    "baseline_build_ms", "ir_pack_ms", "plan_untraced_ms",
}


MS = 1_000_000


def with_program_spans():
    """The synthetic trace with flat ``repro.*`` spans inside the requests
    that cover no gap's larger half, and one outside the window."""
    return test_harness_trace.synthetic() + [
        ["host", "repro.grid.state", 0, 4 * MS],
        ["host", "repro.fused.call", 10 * MS, 40 * MS],
        ["host", "repro.grid.state", 60 * MS, 62 * MS],
        ["host", "repro.ir.timing", 70 * MS, 75 * MS],
        ["host", "repro.grid.state", 110 * MS, 120 * MS],
    ]


def _host(events):
    return [e for e in events if e[0] == "host"]


def test_program_spans_change_no_device_number():
    before = trace_reduce.reduce_trace(test_harness_trace.synthetic())
    assert trace_reduce.reduce_trace(with_program_spans()) == before
    added = set(host_spans.summarize(_host(with_program_spans())))
    added -= set(host_spans.summarize(_host(test_harness_trace.synthetic())))
    assert added == {"repro.grid.state", "repro.fused.call", "repro.ir.timing"}


def test_host_spans_count_and_time_each_name_inside_the_window():
    spans = host_spans.summarize(_host(with_program_spans()))
    assert spans["repro.grid.state"] == {"count": 2, "seconds": pytest.approx(0.006)}
    assert spans["repro.fused.call"] == {"count": 1, "seconds": pytest.approx(0.03)}
    assert spans["repro.ir.timing"] == {"count": 1, "seconds": pytest.approx(0.005)}
    assert spans["bench.request"] == {"count": 2, "seconds": pytest.approx(0.1)}
    assert spans["bench.window"] == {"count": 1, "seconds": pytest.approx(0.1)}
    assert spans["np.asarray(jax.Array)"]["count"] == 1


@pytest.mark.parametrize("window_span", [True, False])
def test_the_window_is_the_device_reductions_window(window_span):
    events = [e for e in with_program_spans()
              if window_span or e[1] != "bench.window"]
    lo, hi = host_spans.window(_host(events))
    assert (hi - lo) / 1e9 == trace_reduce.reduce_trace(events)["window_s"]


def test_no_bench_span_no_host_spans():
    assert host_spans.summarize([["host", "repro.grid.state", 0, 4 * MS]]) == {}


def test_a_gap_is_labelled_by_the_program_span_that_covers_it():
    events = test_harness_trace.synthetic() + [
        ["host", "repro.grid.baseline", 0, 10 * MS],
        ["host", "repro.grid.materialize", 85 * MS, 100 * MS],
    ]
    labels = dict(
        (round(g[1], 6), g[0])
        for g in trace_reduce.reduce_trace(events)["breakdown"]["idle_gaps"]
    )
    assert labels[0.01] == "bench.request > repro.grid.baseline"
    assert labels[0.015] == "bench.request > repro.grid.materialize"
    assert labels[0.03] == "bench.request > np.asarray(jax.Array)"


@pytest.fixture(scope="module")
def runs():
    """One traced run per planner: its result, records and context."""
    out = {}
    for planner, name in (("fused", FUSED_CELL), ("step", STEP_CELL)):
        cell = harness.small_grid_cell(planner, name=name)
        result = harness.run_cell(cell, seconds=0.5, trace=1)
        events = trace_reduce.load_events(
            harness.BENCH / ".traces" / name
        )
        per_request = cell.traffic["n_sizes"] * cell.traffic["n_delays"]
        ctx = types.SimpleNamespace(
            trace=trace_reduce.reduce_trace(events),
            requests=result["attempted"] // per_request,
        )
        out[planner] = (cell, result, events, ctx)
    return out


def _readers():
    cell = harness.bench_run.Cell(harness.spec(), FUSED_CELL)
    cell.per_layer = [m for m in harness.spec()["per_layer"]
                      if m["name"] in NEW]
    return cell.readers()


@pytest.mark.parametrize("planner", ["fused", "step"])
def test_each_reader_reads_its_cells(runs, planner):
    cell, result, _, _ = runs[planner]
    assert result["correct"], result["compared"]
    listed = {m["name"] for m in cell.per_layer} & NEW
    assert listed == NEW - {"step_loop_ms" if planner == "fused"
                            else "fused_call_ms"}
    for name in listed:
        assert result["metrics"][name]["value"] > 0, name
        assert result["metrics"][name]["unit"] == "ms"


@pytest.mark.parametrize("planner,silent", [("fused", "step_loop_ms"),
                                            ("step", "fused_call_ms")])
def test_a_planner_reader_reads_nothing_on_the_other_planner(
    runs, planner, silent
):
    readers = _readers()
    ctx = runs[planner][3]
    assert readers[silent].read(ctx) is None
    assert all(readers[n].read(ctx) > 0 for n in NEW - {silent})


@pytest.mark.parametrize("planner", ["fused", "step"])
def test_the_run_is_found_by_its_window(runs, planner):
    """Both runs' traces lie under ``bench/.traces``; each context finds its
    own, whose host records are those the device reduction loaded."""
    events, ctx = runs[planner][2], runs[planner][3]
    assert host_spans.of(ctx) == host_spans.summarize(_host(events))
    files = list((harness.BENCH / ".traces" / runs[planner][0].name)
                 .rglob("*.xplane.pb"))
    assert host_spans.load_host(files[-1]) == _host(events)


def test_nothing_to_read_without_program_spans(runs, monkeypatch):
    """The parent's program writes no ``repro.*`` span: every new reader
    returns nothing and none raises."""
    ctx = runs["fused"][3]
    spans = {n: s for n, s in host_spans.of(ctx).items()
             if not n.startswith("repro.")}
    monkeypatch.setattr(host_spans, "of", lambda _ctx: spans)
    assert all(r.read(ctx) is None for r in _readers().values())


def test_nothing_to_read_without_the_runs_trace(runs):
    ctx = runs["fused"][3]
    lost = types.SimpleNamespace(trace=dict(ctx.trace, window_s=-1.0),
                                 requests=ctx.requests)
    assert all(r.read(lost) is None for r in _readers().values())


@pytest.mark.parametrize("planner", ["fused", "step"])
def test_program_spans_are_flat_and_inside_requests(runs, planner):
    events = runs[planner][2]
    program = sorted(
        (e for e in events if e[0] == "host" and e[1].startswith("repro.")),
        key=lambda e: e[2],
    )
    requests = [e for e in events if e[1] == "bench.request"]
    assert program and requests
    for a, b in zip(program, program[1:]):
        assert a[3] <= b[2], (a, b)
    for e in program:
        assert any(r[2] <= e[2] and e[3] <= r[3] for r in requests), e
