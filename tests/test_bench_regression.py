"""Tests for the CI benchmark-regression gate (benchmarks/check_regression.py).

The checker is loaded by file path (the benchmarks directory is not on
the tier-1 PYTHONPATH), exercised against synthetic baseline/current
JSON pairs: identical runs pass, an injected 30% regression fails on a
25% band, and silently dropped gate points fail too.
"""

import copy
import importlib.util
import json
import pathlib

import pytest

_CHECKER = (
    pathlib.Path(__file__).resolve().parent.parent
    / "benchmarks"
    / "check_regression.py"
)
_spec = importlib.util.spec_from_file_location("check_regression", _CHECKER)
check_regression = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(check_regression)


_SWEEP = {
    "quick": True,
    "points": [
        {"name": "fig5_swot_milp", "us_per_call": 1200.0, "note": ""},
        {"name": "mt_t2_p4_r200us_cct", "us_per_call": 700.0, "note": ""},
        # Wall-clock rows: machine-dependent, must be ignored.
        {"name": "fig5_wall_time", "us_per_call": 9e5, "note": ""},
        {"name": "ir_sweep_batched_numpy", "us_per_call": 25.0, "note": ""},
        {"name": "indep_grid_batched", "us_per_call": 200.0, "note": ""},
        # Higher-is-better observability rows: gated on *falling*.
        {
            "name": "attr_rab8x4_t200_overlap_eff",
            "us_per_call": 0.85,
            "note": "",
        },
        {
            "name": "bypass_pairwise8x4_t3200_bypass_hit_rate",
            "us_per_call": 0.33,
            "note": "",
        },
        # Per-phase wall-clock + replay throughput: machine-dependent.
        {"name": "mt_phase_replay_us", "us_per_call": 2.6e6, "note": ""},
        {"name": "mt_events_per_sec", "us_per_call": 40.0, "note": ""},
        # Lower-is-better fraction rows: gated with an ABSOLUTE band.
        {
            "name": "mt_scale_qwen3_4b_deadline_miss_rate",
            "us_per_call": 0.08,
            "note": "",
        },
        {
            "name": "mt_scale_gemma_2b_deadline_miss_rate",
            "us_per_call": 0.0,  # a zero baseline must stay gateable
            "note": "",
        },
        {
            "name": "model_trace_site_gemma_2b_tp_act_allreduce_exposed_frac",
            "us_per_call": 0.91,
            "note": "",
        },
    ],
}
_BACKENDS = {
    "backends": {
        "numpy": {"ms": 100.0, "speedup_vs_numpy": 1.0},
        "jax": {"ms": 30.0, "speedup_vs_numpy": 3.3},
    },
    "independent_grid": {"grid_ms": 50.0, "speedup_vs_per_instance": 3.0},
}


def _write(directory: pathlib.Path, sweep: dict, backends: dict) -> None:
    directory.mkdir(parents=True, exist_ok=True)
    (directory / "BENCH_sweep.json").write_text(json.dumps(sweep))
    (directory / "BENCH_backends.json").write_text(json.dumps(backends))


@pytest.fixture
def baseline(tmp_path):
    d = tmp_path / "baseline"
    _write(d, _SWEEP, _BACKENDS)
    return d


def test_identical_runs_pass(baseline, tmp_path):
    current = tmp_path / "current"
    _write(current, _SWEEP, _BACKENDS)
    assert check_regression.compare(baseline, current, 0.25) == []


def test_injected_30pct_regression_fails(baseline, tmp_path):
    sweep = copy.deepcopy(_SWEEP)
    sweep["points"][1]["us_per_call"] *= 1.30  # CCT point up 30%
    backends = copy.deepcopy(_BACKENDS)
    # Ratio floors are clamped to the in-bench hard gate (2x), so the
    # injected ratio drop must land below the gate to register.
    backends["backends"]["jax"]["speedup_vs_numpy"] = 1.8
    current = tmp_path / "current"
    _write(current, sweep, backends)
    failures = check_regression.compare(baseline, current, 0.25)
    assert len(failures) == 2
    assert any("mt_t2_p4_r200us_cct" in f for f in failures)
    assert any("backend_speedup:jax" in f for f in failures)


def test_ratio_drop_above_hard_gate_passes(baseline, tmp_path):
    """A fast-host baseline must not fail a slower runner that still
    clears the benchmark's own >= 2x gate (the band floor is clamped)."""
    backends = copy.deepcopy(_BACKENDS)
    backends["backends"]["jax"]["speedup_vs_numpy"] = 2.1  # -36% vs 3.3
    backends["independent_grid"]["speedup_vs_per_instance"] = 2.05
    current = tmp_path / "current"
    _write(current, _SWEEP, backends)
    assert check_regression.compare(baseline, current, 0.25) == []


def test_regressions_inside_the_band_pass(baseline, tmp_path):
    sweep = copy.deepcopy(_SWEEP)
    sweep["points"][1]["us_per_call"] *= 1.20  # within the 25% band
    backends = copy.deepcopy(_BACKENDS)
    backends["backends"]["jax"]["speedup_vs_numpy"] *= 0.80
    current = tmp_path / "current"
    _write(current, sweep, backends)
    assert check_regression.compare(baseline, current, 0.25) == []


def test_wall_clock_rows_are_ignored(baseline, tmp_path):
    sweep = copy.deepcopy(_SWEEP)
    for pt in sweep["points"]:
        if pt["name"] in (
            "fig5_wall_time", "ir_sweep_batched_numpy", "indep_grid_batched"
        ):
            pt["us_per_call"] *= 10.0  # huge, but machine-dependent
    current = tmp_path / "current"
    _write(current, sweep, _BACKENDS)
    assert check_regression.compare(baseline, current, 0.25) == []


def test_higher_better_drop_fails(baseline, tmp_path):
    """overlap_eff / hit_rate rows regress by FALLING below the band."""
    sweep = copy.deepcopy(_SWEEP)
    for pt in sweep["points"]:
        if check_regression._HIGHER_BETTER.search(pt["name"]):
            pt["us_per_call"] *= 0.5  # -50%, past the 25% band
    current = tmp_path / "current"
    _write(current, sweep, _BACKENDS)
    failures = check_regression.compare(baseline, current, 0.25)
    assert len(failures) == 2
    assert any("overlap_eff" in f for f in failures)
    assert any("hit_rate" in f for f in failures)


def test_higher_better_rise_passes(baseline, tmp_path):
    """A doubled efficiency would trip the lower-is-better branch; the
    suffix must route it to the higher-is-better one instead."""
    sweep = copy.deepcopy(_SWEEP)
    for pt in sweep["points"]:
        if check_regression._HIGHER_BETTER.search(pt["name"]):
            pt["us_per_call"] *= 2.0
    current = tmp_path / "current"
    _write(current, sweep, _BACKENDS)
    assert check_regression.compare(baseline, current, 0.25) == []


def test_rate_rise_past_absolute_band_fails(baseline, tmp_path):
    """miss_rate / exposed_frac rows regress by RISING more than the
    band *absolutely* -- including from a 0.0 baseline, where any
    relative rule would be vacuous."""
    sweep = copy.deepcopy(_SWEEP)
    for pt in sweep["points"]:
        if check_regression._RATE_ROW.search(pt["name"]):
            pt["us_per_call"] += 0.30  # past the 0.25 absolute band
    current = tmp_path / "current"
    _write(current, sweep, _BACKENDS)
    failures = check_regression.compare(baseline, current, 0.25)
    assert len(failures) == 3
    assert any("deadline_miss_rate" in f for f in failures)
    assert any("exposed_frac" in f for f in failures)
    assert any("gemma_2b_deadline_miss_rate" in f for f in failures)


def test_rate_within_band_or_improving_passes(baseline, tmp_path):
    sweep = copy.deepcopy(_SWEEP)
    for pt in sweep["points"]:
        if pt["name"] == "mt_scale_qwen3_4b_deadline_miss_rate":
            pt["us_per_call"] = 0.0  # improvement: fewer misses
        if pt["name"] == "mt_scale_gemma_2b_deadline_miss_rate":
            pt["us_per_call"] = 0.2  # rise, but inside the 0.25 band
        if pt["name"].endswith("_exposed_frac"):
            pt["us_per_call"] *= 0.5
    current = tmp_path / "current"
    _write(current, sweep, _BACKENDS)
    assert check_regression.compare(baseline, current, 0.25) == []


def test_phase_timing_and_throughput_rows_are_ignored(baseline, tmp_path):
    """``mt_phase_*_us`` and ``mt_events_per_sec`` are wall-clock derived:
    arbitrary machine-to-machine swings must not gate."""
    sweep = copy.deepcopy(_SWEEP)
    for pt in sweep["points"]:
        if pt["name"] == "mt_phase_replay_us":
            pt["us_per_call"] *= 10.0
        if pt["name"] == "mt_events_per_sec":
            pt["us_per_call"] *= 0.1
    current = tmp_path / "current"
    _write(current, sweep, _BACKENDS)
    assert check_regression.compare(baseline, current, 0.25) == []


def test_dropped_gate_point_fails(baseline, tmp_path):
    sweep = copy.deepcopy(_SWEEP)
    sweep["points"] = [
        p for p in sweep["points"] if p["name"] != "fig5_swot_milp"
    ]
    backends = copy.deepcopy(_BACKENDS)
    del backends["independent_grid"]
    current = tmp_path / "current"
    _write(current, sweep, backends)
    failures = check_regression.compare(baseline, current, 0.25)
    assert any("fig5_swot_milp" in f for f in failures)
    assert any("independent_grid_speedup" in f for f in failures)


def test_improvements_pass(baseline, tmp_path):
    sweep = copy.deepcopy(_SWEEP)
    sweep["points"][0]["us_per_call"] *= 0.5  # better CCT
    backends = copy.deepcopy(_BACKENDS)
    backends["backends"]["jax"]["speedup_vs_numpy"] *= 2.0
    current = tmp_path / "current"
    _write(current, sweep, backends)
    assert check_regression.compare(baseline, current, 0.25) == []


def test_cli_exit_codes(baseline, tmp_path):
    current = tmp_path / "current"
    _write(current, _SWEEP, _BACKENDS)
    assert (
        check_regression.main(
            ["--baseline", str(baseline), "--current", str(current)]
        )
        == 0
    )
    sweep = copy.deepcopy(_SWEEP)
    sweep["points"][0]["us_per_call"] *= 1.5
    _write(current, sweep, _BACKENDS)
    assert (
        check_regression.main(
            ["--baseline", str(baseline), "--current", str(current)]
        )
        == 1
    )
