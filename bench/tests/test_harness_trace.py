"""The reduction from profiler records to the device's numbers: busy time
as a union, the idle share, time per XLA program, and the idle gaps
labelled by what the host was doing."""

import json
import pathlib

import harness  # noqa: F401  (puts the benchmark on sys.path)
import pytest
import trace_reduce

DATA = pathlib.Path(__file__).parent / "data"
DEV = "/device:TPU:0"


def synthetic():
    ms = 1_000_000
    return [
        ["host", "bench.window", 0, 100 * ms],
        ["host", "bench.request", 0, 60 * ms],
        ["host", "np.asarray(jax.Array)", 40 * ms, 60 * ms],
        ["host", "bench.request", 60 * ms, 100 * ms],
        [f"module@{DEV}", "jit_run(123)", 10 * ms, 40 * ms],
        [f"module@{DEV}", "jit_fn(9)", 70 * ms, 75 * ms],
        [f"module@{DEV}", "jit_fn(9)", 80 * ms, 85 * ms],
        # A loop op holding two body ops, and a partly overlapping op.
        [f"op@{DEV}", "%while.1 = (u32[]) while(...)", 10 * ms, 40 * ms],
        [f"op@{DEV}", "%fusion.2 = f32[8] fusion(...)", 10 * ms, 25 * ms],
        [f"op@{DEV}", "%fusion.3 = f32[8] fusion(...)", 25 * ms, 40 * ms],
        [f"op@{DEV}", "%fusion.4 = f32[8] fusion(...)", 70 * ms, 75 * ms],
        [f"op@{DEV}", "%fusion.4 = f32[8] fusion(...)", 80 * ms, 85 * ms],
        # Outside the window: ignored.
        [f"op@{DEV}", "%fusion.9 = f32[8] fusion(...)", 120 * ms, 130 * ms],
    ]


def test_busy_is_the_union_and_idle_its_complement():
    t = trace_reduce.reduce_trace(synthetic())
    assert t["window_s"] == pytest.approx(0.1)
    assert t["busy_s"] == pytest.approx(0.040)  # 30 + 5 + 5 ms, no double count
    assert t["idle_pct"] == pytest.approx(60.0)
    assert t["devices"] == 1


def test_time_per_program():
    t = trace_reduce.reduce_trace(synthetic())
    assert t["programs"]["jit_run"] == {"runs": 1, "seconds": pytest.approx(0.03)}
    assert t["programs"]["jit_fn"] == {"runs": 2, "seconds": pytest.approx(0.01)}


def test_device_ops_count_leaves_only():
    ops = dict(trace_reduce.reduce_trace(synthetic())["breakdown"]["device_ops"])
    assert "while.1" not in ops
    assert ops["fusion.2"] == pytest.approx(0.015)
    assert ops["fusion.4"] == pytest.approx(0.010)


def test_gaps_are_labelled_by_the_host():
    gaps = trace_reduce.reduce_trace(synthetic())["breakdown"]["idle_gaps"]
    # Gaps: 0-10, 40-70, 75-80, 85-100 ms; the longest first.
    assert [round(g[1], 6) for g in gaps] == [0.03, 0.015, 0.01, 0.005]
    assert gaps[0][0] == "bench.request > np.asarray(jax.Array)"
    assert gaps[1][0] == "bench.request > python"


def test_without_the_window_span_its_calls_bound_the_window():
    t = trace_reduce.reduce_trace([e for e in synthetic() if e[1] != "bench.window"])
    assert t["window_s"] == pytest.approx(0.1)
    assert t["busy_s"] == pytest.approx(0.040)


def test_no_span_at_all_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce_trace([e for e in synthetic() if not e[1].startswith("bench.")])


@pytest.mark.parametrize("path", sorted(DATA.glob("events_*.json")), ids=lambda p: p.stem)
def test_recorded_trace(path):
    """A trace recorded on the chip: the reduction agrees with a
    brute-force union on a 10 us grid, and finds the cell's programs."""
    events = json.loads(path.read_text())
    t = trace_reduce.reduce_trace(events)
    win = next(e for e in events if e[1] == "bench.window")
    step = 10_000
    covered = set()
    for e in events:
        if e[0].startswith("op@"):
            lo, hi = max(e[2], win[2]), min(e[3], win[3])
            covered.update(range(lo // step, (hi + step - 1) // step))
    approx_busy = len(covered) * step / 1e9
    assert 0.0 < t["busy_s"] <= t["window_s"]
    assert t["busy_s"] == pytest.approx(approx_busy, rel=0.05, abs=2e-4)
    assert "jit_fn" in t["programs"]
    assert len(t["breakdown"]["idle_gaps"]) <= 10
    assert sum(p["seconds"] for p in t["programs"].values()) <= t["window_s"] * t["devices"]
