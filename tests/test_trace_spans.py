"""Wall-clock host spans of the grid planner (`repro.obs.trace.host_span`).

* ``plan()`` on a grid writes the seven ``repro.*`` spans into the JAX
  profiler's trace, each with its call's ``request`` number and the grid's
  ``cells``: one number within a call, another for the next call; the
  fused CHAIN call also names the cell count its scan ran at
  (``padded``: the power-of-two bucket where the chip rule applies);
* ``host_span`` is a shared null context while nothing has imported jax,
  and importing ``repro.obs.trace`` imports neither jax nor `repro.core`;
* a grid planned with Topology Bypassing adds the twin pass's four flat
  ``repro.relay.*`` spans (state, call, materialize, pick) and grows the
  planner's relay tally (``greedy.RELAY_TALLY``);
* the planner's XLA programs keep the names the benchmark reads: the
  CHAIN scan ``jit_run`` (``fused_scan_ms``), its relay twin
  ``jit_relay_run`` (``relay_scan_ms``) and the timing scan ``jit_fn``
  (``timing_scan_ms``), and no other planner program takes any of them.
"""

import pathlib
import re
import subprocess
import sys

import pytest

from repro.core import OpticalFabric, get_pattern
from repro.core.api import PlannerOptions, PlanRequest, plan
from repro.core.schedule import DependencyMode
from repro.obs import trace

jax = pytest.importorskip("jax")

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"
SPANS = {
    "repro.grid.state",
    "repro.grid.step_loop",
    "repro.fused.call",
    "repro.grid.materialize",
    "repro.grid.baseline",
    "repro.ir.pack",
    "repro.ir.timing",
}


def _cells():
    return [
        (
            OpticalFabric(16, 4, bandwidth=25e9, t_recfg=t_recfg),
            get_pattern("pairwise_alltoall", 16, size),
        )
        for size in (1e6, 4e6)
        for t_recfg in (1e-5, 1e-4)
    ]


def _request(planner: str) -> PlanRequest:
    options = PlannerOptions(
        planner=planner, backend="jax", max_enumerated_planes=4
    )
    return PlanRequest.grid(_cells(), options=options)


def _program_spans(trace_dir: pathlib.Path) -> list[tuple]:
    """``(name, start_ns, end_ns, args)`` of every ``repro.*`` span."""
    from jax.profiler import ProfileData

    (path,) = trace_dir.rglob("*.xplane.pb")
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    start = int(e.start_ns)
                    spans.append((e.name, start, start + int(e.duration_ns),
                                  dict(e.stats)))
    return sorted(spans, key=lambda s: s[1])


def test_plan_spans_carry_one_request_number_per_call(tmp_path):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=options):
        plan(_request("fused"))
        plan(_request("step"))
    spans = _program_spans(tmp_path)
    assert {s[0] for s in spans} == SPANS
    assert all(s[3]["cells"] == 4 for s in spans)
    calls: dict[int, list] = {}
    for s in spans:
        calls.setdefault(s[3]["request"], []).append(s)
    assert len(calls) == 2
    first, second = (calls[n] for n in sorted(calls))
    assert first[-1][2] <= second[0][1]  # one call's spans, then the next's
    assert {s[0] for s in first} == SPANS - {"repro.grid.step_loop"}
    assert {s[0] for s in second} == SPANS - {"repro.fused.call"}
    # Flat: no program span encloses another.
    for a, b in zip(spans, spans[1:]):
        assert a[2] <= b[1], (a, b)


RELAY_SPANS = {
    "repro.relay.state",
    "repro.relay.call",
    "repro.relay.materialize",
    "repro.relay.pick",
}


@pytest.mark.parametrize("planner", ["fused", "step"])
def test_bypass_grid_writes_the_relay_spans_and_grows_the_tally(
    tmp_path, planner
):
    from repro.core import greedy

    cells = [
        (
            OpticalFabric(16, 4, bandwidth=25e9, t_recfg=t_recfg),
            get_pattern("bruck_alltoall", 16, size),
        )
        for size in (1e6, 4e7)
        for t_recfg in (1e-3, 3e-3)
    ]
    options = PlannerOptions(
        planner=planner, backend="jax", max_enumerated_planes=4,
        bypass_depth=2,
    )
    before = greedy.RELAY_TALLY.snapshot()
    profile = jax.profiler.ProfileOptions()
    profile.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=profile):
        result = plan(PlanRequest.grid(cells, options=options))
    after = greedy.RELAY_TALLY.snapshot()
    spans = _program_spans(tmp_path)
    names = {s[0] for s in spans}
    assert names >= RELAY_SPANS
    assert names - RELAY_SPANS == SPANS - {
        "repro.grid.step_loop" if planner == "fused" else "repro.fused.call"
    }
    assert all(s[3]["cells"] == 4 for s in spans)
    for a, b in zip(spans, spans[1:]):
        assert a[2] <= b[1], (a, b)
    kept = [g for g in result.grid if g.plan.decisions.bypass]
    assert after["twin_cells"] - before["twin_cells"] == 4
    # One Bruck-16 mapping across both sizes: one depth table.
    assert after["depth_tables"] - before["depth_tables"] == 1
    assert after["relay_cells"] - before["relay_cells"] == len(kept) > 0
    assert after["relay_routes"] - before["relay_routes"] == sum(
        len(routes) for g in kept for routes in g.plan.decisions.bypass
    )


def _fused_call_args(tmp_path, n_cells: int) -> dict:
    options = PlannerOptions(planner="fused", max_enumerated_planes=4)
    request = PlanRequest.grid(_cells()[:n_cells], options=options)
    profile = jax.profiler.ProfileOptions()
    profile.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=profile):
        plan(request)
    (call,) = [s for s in _program_spans(tmp_path)
               if s[0] == "repro.fused.call"]
    return call[3]


def test_fused_call_span_names_the_padded_bucket(tmp_path, monkeypatch):
    # Where the chip rule applies (a small CHAIN grid on a TPU).
    from repro.core.ir import backends

    monkeypatch.setattr(backends, "_on_tpu", lambda: True)
    args = _fused_call_args(tmp_path, 3)
    assert args["cells"] == 3
    assert args["padded"] == 4


def test_fused_call_span_is_unpadded_off_the_chip(tmp_path):
    args = _fused_call_args(tmp_path, 3)
    assert args["cells"] == 3
    assert args["padded"] == 3


def test_host_span_is_a_null_context_without_jax(monkeypatch):
    monkeypatch.delitem(sys.modules, "jax")
    span = trace.host_span("repro.grid.state", cells=4)
    assert span is trace.host_span("repro.ir.pack")
    with span:
        pass


def test_request_scope_numbers_each_call():
    with trace.request_scope() as first:
        with trace.request_scope() as inner:
            assert inner > first
        assert trace._REQUEST.get() == first
    with trace.request_scope() as second:
        assert second > inner
    assert trace._REQUEST.get() is None


def test_importing_the_helper_imports_neither_jax_nor_the_core():
    code = (
        "import sys, repro.obs.trace\n"
        "assert 'jax' not in sys.modules\n"
        "assert 'repro.core' not in sys.modules\n"
        "import repro.core, repro.obs\n"
        "assert repro.obs.host_span is repro.obs.trace.host_span\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={"PYTHONPATH": str(SRC), "JAX_PLATFORMS": "cpu"}, timeout=120,
    )
    assert out.returncode == 0, out.stderr


def _program_name(lowered) -> str:
    return re.search(r"module @(\S+)", lowered.as_text()).group(1)


def test_program_names_the_benchmark_reads():
    from repro.core.baselines import strawman_instance
    from repro.core.greedy import _GridState
    from repro.core.ir import fused
    from repro.core.ir.backends import JaxBackend, _build_jax_timing, x64
    from repro.core.ir.engine import pack_instances

    chain = _GridState(_cells(), max_enumerated_planes=4, bypass_depth=2)
    indep = _GridState(_cells(), mode=DependencyMode.INDEPENDENT)
    packed = pack_instances(
        [strawman_instance(f, p) for f, p in _cells()], None
    )
    padded, _ = JaxBackend()._padded(packed)
    timing_args = [
        padded[k] for k in (
            "vol", "step_vol", "step_cfg", "step_mask", "plane_mask", "bw",
            "init", "t_recfg", "chain", "ready", "byp_vol", "byp_plane",
        )
    ]
    with x64():
        names = {
            ("chain", bypass): _program_name(
                fused._chain_scan(24, bypass).lower(
                    fused._chain_tables(chain, bypass)
                )
            )
            for bypass in (False, True)
        }
        for split in (False, True):
            names[("independent", split)] = _program_name(
                fused._independent_scan(split).lower(
                    fused._base_tables(indep)
                )
            )
        for att in (False, True):
            names[("timing", att)] = _program_name(
                _build_jax_timing(att).lower(*timing_args)
            )
    assert names[("chain", False)] == "jit_run"
    assert names[("chain", True)] == "jit_relay_run"
    assert names[("timing", False)] == names[("timing", True)] == "jit_fn"
    others = {v for k, v in names.items() if k[0] == "independent"}
    assert len(others) == 2
    assert not others & {"jit_run", "jit_relay_run", "jit_fn"}
