"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Modules:

* fig5_motivation  -- paper Fig. 5 (exact published CCTs)
* fig7_cct_vs_msgsize -- paper Fig. 7(a-c)
* fig8_scalability -- paper Fig. 8(a-b)
* scheduler_bench  -- solve-time vs the paper's Gurobi claim
* kernel_bench     -- Pallas kernel microbenches (interpret mode)
* swot_ladder      -- optical scheduling modes on a real step's
                      collectives (EXPERIMENTS.md section 4.1)
* multi_tenant_bench -- concurrent collectives on a shared fabric
                      (tenants x planes x t_recfg sweep)
* ir_sweep         -- batched array-IR scenario sweep vs the
                      per-instance object path (>= 5x gate)

Usage: ``python benchmarks/run.py [module-substring] [--quick]``.
``--quick`` runs a single-cell smoke sweep per module that supports it
(CI uses this).

Every unfiltered run (no module substring) also writes
``BENCH_sweep.json`` at the repo root: the same per-point values (CCTs
in us for schedule points, wall-clock in us for scheduling/validation
points) plus per-module wall-clock seconds, so the perf trajectory is
machine-readable across PRs.  Module-filtered runs skip the write, and
full (non ``--quick``) sweeps write ``BENCH_sweep_full.json`` instead,
so neither ever clobbers the tracked file.  The committed flavor is the
``--quick`` output (the cell CI runs every PR) — regenerate it with
``PYTHONPATH=src:. python benchmarks/run.py --quick`` when benchmarks
change.

Unfiltered runs additionally write the IR timing-backend throughput
comparison (numpy vs jax vs pallas-interpret on the large ``ir_sweep``
grid, cold/compile and warm timed separately, including the >= 2x
jax-vs-numpy acceptance gate) plus the fused on-device planner gate
(``fused_grid``: the whole CHAIN greedy loop as one jitted ``lax.scan``,
>= 2x warm vs the per-step numpy loop with 0 decision mismatches):
``BENCH_backends.json`` for ``--quick`` (the tracked, CI-comparable
flavor) and ``BENCH_backends_full.json`` otherwise, so backend speedups
are tracked across PRs alongside the sweep numbers.
"""

import json
import pathlib
import sys
import time

_REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_REPO_ROOT / "src"))

from repro import use_compile_cache  # noqa: E402
from repro.obs import get_logger  # noqa: E402

log = get_logger("benchmarks")


def main() -> None:
    use_compile_cache()
    from benchmarks import (
        fig5_motivation,
        fig7_cct_vs_msgsize,
        fig8_scalability,
        ir_sweep,
        kernel_bench,
        multi_tenant_bench,
        scheduler_bench,
        swot_ladder,
    )

    modules = [
        fig5_motivation,
        fig7_cct_vs_msgsize,
        fig8_scalability,
        scheduler_bench,
        kernel_bench,
        swot_ladder,
        multi_tenant_bench,
        ir_sweep,
    ]
    args = [a for a in sys.argv[1:]]
    quick = "--quick" in args
    args = [a for a in args if a != "--quick"]
    only = args[0] if args else None
    points: list[dict] = []
    module_wall: dict[str, float] = {}
    # CSV rows are the program's machine-readable contract -- they go
    # through the always-on data channel; REPRO_LOG only affects the
    # narrative channel.
    log.data("name,us_per_call,derived")
    for module in modules:
        if only and only not in module.__name__:
            continue
        t_wall = time.perf_counter()
        if quick:
            import inspect

            if "quick" in inspect.signature(module.run).parameters:
                rows = module.run(quick=True)
            elif only or module is fig5_motivation:
                rows = module.run()  # cheap (or explicitly requested)
            else:
                continue  # no quick mode: skipped in CI smoke runs
        else:
            rows = module.run()
        module_wall[module.__name__] = time.perf_counter() - t_wall
        for name, us, note in rows:
            log.data(f"{name},{us:.1f},{note}")
            points.append(
                {"name": name, "us_per_call": round(us, 3), "note": note}
            )
    if only:
        return  # partial run: don't clobber the tracked sweep file
    # Backend throughput comparison (and the jax >= 2x gate) on the
    # large grid; its own JSON so the trajectory file stays diffable.
    # Same no-clobber policy as the sweep file: the tracked name holds
    # the CI-comparable --quick flavor, full runs land in a sibling.
    backends_payload = ir_sweep.backend_throughput(quick=quick)
    for name, entry in backends_payload["backends"].items():
        note = (
            "unavailable"
            if "ms" not in entry
            else f"total={entry['ms']:.1f}ms "
            f"speedup={entry['speedup_vs_numpy']}x "
            f"compile={entry['compile_ms']:.1f}ms"
        )
        us = entry.get("us_per_instance", 0.0)
        log.data(f"ir_backend_{name},{us:.1f},{note}")
    fused = backends_payload["fused_grid"]
    log.data(
        f"fused_grid,{fused['us_per_cell']:.1f},"
        f"per_step={fused['per_step_ms']:.0f}ms "
        f"warm={fused['fused_warm_ms']:.0f}ms "
        f"cold={fused['fused_cold_ms']:.0f}ms "
        f"speedup={fused['speedup_vs_per_step']}x "
        f"mismatches={fused['decision_mismatches']}"
    )
    # Machine-independent runtime-scale ratio (warm memoized replay vs
    # the legacy per-event path, measured in the same run) -- hard-gated
    # by check_regression.py alongside the backend speedups.
    by_name = {p["name"]: p for p in points}
    if "mt_scale_speedup" in by_name:
        backends_payload["multi_tenant_scale"] = {
            "speedup_vs_serial_path": by_name["mt_scale_speedup"][
                "us_per_call"
            ],
            "cache_hit_rate": by_name.get("mt_cache_hit_rate", {}).get(
                "us_per_call"
            ),
            "note": by_name["mt_scale_speedup"]["note"],
        }
    backends_name = (
        "BENCH_backends.json" if quick else "BENCH_backends_full.json"
    )
    (_REPO_ROOT / backends_name).write_text(
        json.dumps(backends_payload, indent=1) + "\n"
    )
    payload = {
        "quick": quick,
        "module_wall_clock_s": {
            k: round(v, 4) for k, v in module_wall.items()
        },
        "points": points,
    }
    # The tracked file holds only the CI-comparable --quick flavor; full
    # local sweeps land in an untracked sibling.
    name = "BENCH_sweep.json" if quick else "BENCH_sweep_full.json"
    (_REPO_ROOT / name).write_text(json.dumps(payload, indent=1) + "\n")


if __name__ == "__main__":
    main()
