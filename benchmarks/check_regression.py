"""CI benchmark-regression gate: diff emitted BENCH JSONs vs baselines.

``benchmarks/run.py --quick`` emits ``BENCH_sweep.json`` and
``BENCH_backends.json``; this script compares a fresh pair against the
committed baselines with a tolerance band and exits non-zero on
regression, so the BENCH_* numbers are enforced by the pipeline instead
of rotting silently.

What is gated (and why):

* **Deterministic sweep points** -- every ``BENCH_sweep.json`` point
  that is not a wall-clock timing row (CCTs, queueing delays,
  utilization: simulated quantities, identical on any machine).  A
  value drifting above baseline by more than the band fails.  This
  includes the Topology-Bypassing rows (``bypass_*_cct`` per-point CCTs
  and ``bypass_*_cct_ratio`` bypass/no-bypass ratios, which are <= 1 by
  the guarded pick): a bypass CCT reduction that shrinks past the band
  fails here, on top of the strict in-run gate ``ir_sweep.bypass_sweep``
  asserts at the documented high-``t_recfg`` point.
* **Higher-is-better points** -- deterministic rows named
  ``*_overlap_eff`` (attributed fraction of reconfiguration time the
  schedule hides behind transmission) and ``*_hit_rate`` (bypass
  steps served by relays): these fail when the current value falls
  *below* baseline by more than the band.
* **Rate points, absolute band** -- fraction-valued lower-is-better
  rows named ``*_miss_rate`` (per-tenant SLO deadline misses on the
  scale replay) and ``*_exposed_frac`` (per-site exposed share of
  reconfiguration time): deterministic simulated fractions in [0, 1],
  failed when the current value exceeds baseline by more than the band
  *absolutely* (baselines of exactly 0.0 stay gateable).
* **Speedup ratios** -- ``speedup_vs_numpy`` per backend from
  ``BENCH_backends.json``, the INDEPENDENT-grid
  ``speedup_vs_per_instance``, the fused-planner
  ``speedup_vs_per_step`` (fused ``lax.scan`` CHAIN planner vs the
  per-step numpy loop), and the runtime-scale
  ``multi_tenant_scale.speedup_vs_serial_path`` (warm memoized replay
  of the 10k-job heavy-tailed trace vs the legacy per-event planning
  path).  Ratios compare two timings from the SAME run on the SAME
  host, so they transfer across runner hardware where absolute
  microseconds do not.  A ratio falling below baseline by more than
  the band fails -- with the floor clamped to the benchmark's own
  in-run hard gate (>= 2x for the backend gates, >= 50x for the
  runtime-scale gate), so a baseline captured on a fast host can
  never fail a slower runner that still clears the gate.
* **Throughput rows, wide band** -- ``*_events_per_sec`` and
  ``*_speedup`` sweep rows are wall-clock derived, so absolute values
  move with runner hardware; they get a deliberately wide
  higher-is-better band (fail only below 10%% of baseline) that still
  catches an order-of-magnitude collapse -- e.g. the hot path
  silently falling back to per-event planning.

What is deliberately NOT gated:

* absolute wall-clock rows (``*_wall_time``, ``ir_sweep_*``,
  ``indep_grid_*``, ``ir_backend_*``, ``fused_grid_*`` microsecond
  columns, including the ``*_compile`` cold-start rows and the
  ``*_us`` phase rows) -- runner hardware varies run to run.

A point present in the baseline but missing from the current run fails
too (a silently dropped gate is itself rot); new points are reported
but pass, since they land together with their regenerated baseline.

Usage (CI runs exactly this)::

    python benchmarks/check_regression.py \
        --baseline /tmp/bench-baseline --current . [--tolerance 0.25]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import re
import sys

sys.path.insert(
    0, str(pathlib.Path(__file__).resolve().parent.parent / "src")
)

from repro.obs import get_logger  # noqa: E402

log = get_logger("check_regression")

# Sweep rows whose us_per_call is a wall-clock measurement (machine
# dependent): excluded from the deterministic-point comparison.  The
# ``_us$`` suffix covers the per-phase timing rows.
_TIMING_ROW = re.compile(
    r"(wall_time|ir_sweep_|indep_grid_|ir_backend_|fused_grid_"
    r"|_solve_time|_us$)"
)
# Deterministic sweep rows where LARGER is better (overlap efficiency,
# bypass/cache hit rate): gated on falling below baseline instead of
# rising above it.
_HIGHER_BETTER = re.compile(r"(overlap_eff|hit_rate|overlap_gain)$")
# Fraction-valued lower-is-better rows (SLO deadline miss rates,
# per-site exposed-reconfiguration fractions): values live in [0, 1]
# and baselines are legitimately 0.0, so the band is *absolute* -- the
# current rate may not exceed baseline + tolerance.  Checked before the
# higher-is-better rule (``deadline_miss_rate`` must not fall through
# to the relative rules).
_RATE_ROW = re.compile(r"(miss_rate|exposed_frac)$")
# Wall-clock-derived throughput rows (events/sec, speedup ratios):
# higher is better, but absolute values track runner hardware, so the
# band is deliberately wide -- only an order-of-magnitude collapse
# (below 10% of baseline) fails.
_WIDE_BAND_ROW = re.compile(r"(events_per_sec|_speedup)$")
_WIDE_BAND = 0.90

# Hard floors the benchmarks themselves assert in-run (ir_sweep's >= 2x
# gates).  The band floor is clamped to never exceed these: a baseline
# captured on a fast host must not make a slower runner fail while it
# still clears the benchmark's own gate -- but a current run whose JSON
# somehow records a sub-gate ratio (e.g. the in-bench assert was
# deleted) still fails here.
_RATIO_HARD_GATES = {
    "backend_speedup:jax": 2.0,
    "independent_grid_speedup": 2.0,
    "fused_grid_speedup": 2.0,
    "mt_scale_speedup": 50.0,
}

SWEEP_NAME = "BENCH_sweep.json"
BACKENDS_NAME = "BENCH_backends.json"


def _load(path: pathlib.Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _sweep_points(payload: dict) -> dict[str, float]:
    return {
        pt["name"]: float(pt["us_per_call"])
        for pt in payload.get("points", [])
        if not _TIMING_ROW.search(pt["name"])
    }


def _speedup_ratios(payload: dict) -> dict[str, float]:
    ratios: dict[str, float] = {}
    for name, entry in payload.get("backends", {}).items():
        if "speedup_vs_numpy" not in entry:
            continue
        ratios[f"backend_speedup:{name}"] = float(
            entry["speedup_vs_numpy"]
        )
    grid = payload.get("independent_grid", {})
    if "speedup_vs_per_instance" in grid:
        ratios["independent_grid_speedup"] = float(
            grid["speedup_vs_per_instance"]
        )
    fused = payload.get("fused_grid", {})
    if "speedup_vs_per_step" in fused:
        ratios["fused_grid_speedup"] = float(fused["speedup_vs_per_step"])
    scale = payload.get("multi_tenant_scale", {})
    if "speedup_vs_serial_path" in scale:
        ratios["mt_scale_speedup"] = float(
            scale["speedup_vs_serial_path"]
        )
    return ratios


def compare(
    baseline_dir: pathlib.Path,
    current_dir: pathlib.Path,
    tolerance: float,
) -> list[str]:
    """Return a list of human-readable failures (empty = pass)."""
    failures: list[str] = []

    base_sweep = _sweep_points(_load(baseline_dir / SWEEP_NAME))
    cur_sweep = _sweep_points(_load(current_dir / SWEEP_NAME))
    for name, base in sorted(base_sweep.items()):
        if name not in cur_sweep:
            failures.append(f"sweep point {name!r} missing from current run")
            continue
        cur = cur_sweep[name]
        if _RATE_ROW.search(name):
            if cur > base + tolerance:
                failures.append(
                    f"rate point {name!r} regressed: {cur:.3f} vs "
                    f"baseline {base:.3f} (+{cur - base:.3f} absolute, "
                    f"band is {tolerance:.2f})"
                )
        elif _WIDE_BAND_ROW.search(name):
            if base > 0 and cur < base * (1.0 - _WIDE_BAND):
                failures.append(
                    f"throughput point {name!r} collapsed: {cur:.1f} vs "
                    f"baseline {base:.1f} ({cur / base - 1.0:.0%}, "
                    f"wide band is {_WIDE_BAND:.0%})"
                )
        elif _HIGHER_BETTER.search(name):
            if base > 0 and cur < base * (1.0 - tolerance):
                failures.append(
                    f"sweep point {name!r} regressed: {cur:.3f} vs "
                    f"baseline {base:.3f} ({cur / base - 1.0:.0%}, "
                    f"higher-is-better band is {tolerance:.0%})"
                )
        elif base > 0 and cur > base * (1.0 + tolerance):
            failures.append(
                f"sweep point {name!r} regressed: {cur:.3f} vs baseline "
                f"{base:.3f} (+{cur / base - 1.0:.0%}, band is "
                f"{tolerance:.0%})"
            )
    for name in sorted(set(cur_sweep) - set(base_sweep)):
        log.info(f"note: new sweep point {name!r} (no baseline yet)")

    base_ratio = _speedup_ratios(_load(baseline_dir / BACKENDS_NAME))
    cur_ratio = _speedup_ratios(_load(current_dir / BACKENDS_NAME))
    for name, base in sorted(base_ratio.items()):
        if name not in cur_ratio:
            failures.append(f"ratio {name!r} missing from current run")
            continue
        cur = cur_ratio[name]
        floor = base * (1.0 - tolerance)
        if name in _RATIO_HARD_GATES:
            floor = min(floor, _RATIO_HARD_GATES[name])
        if base > 0 and cur < floor:
            failures.append(
                f"throughput ratio {name!r} regressed: {cur:.2f}x vs "
                f"baseline {base:.2f}x (floor {floor:.2f}x, band is "
                f"{tolerance:.0%})"
            )
    for name in sorted(set(cur_ratio) - set(base_ratio)):
        log.info(f"note: new ratio {name!r} (no baseline yet)")

    n_checked = len(base_sweep) + len(base_ratio)
    # The verdict is the script's contract (CI greps it): data channel.
    log.data(
        f"checked {len(base_sweep)} sweep points + {len(base_ratio)} "
        f"throughput ratios against {baseline_dir} "
        f"(band {tolerance:.0%}): "
        + ("PASS" if not failures else f"{len(failures)} FAILURE(S)")
    )
    assert n_checked > 0, "baselines contained nothing to check"
    return failures


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--baseline",
        type=pathlib.Path,
        required=True,
        help="directory holding the committed baseline BENCH_*.json",
    )
    parser.add_argument(
        "--current",
        type=pathlib.Path,
        required=True,
        help="directory holding the freshly emitted BENCH_*.json",
    )
    parser.add_argument(
        "--tolerance",
        type=float,
        default=0.25,
        help="relative regression band (default 0.25 = 25%%)",
    )
    args = parser.parse_args(argv)
    failures = compare(args.baseline, args.current, args.tolerance)
    for failure in failures:
        log.warning(f"REGRESSION: {failure}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
