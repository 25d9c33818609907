#!/usr/bin/env python3
"""Bring-up smoke test: the SWOT planner's device path on one TPU chip.

Runs the planner through its public entry points at a size its users
plan at, checks every result against the numpy reference, and prints
one JSON line per phase followed, as the last line, by

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}

Phases:

(a) fused grid planning -- ``repro.core.api.plan`` on the 1,024-cell
    CHAIN grid (128-node pairwise all-to-all, 127 steps, 8 planes;
    32 message sizes of 1-32 MB x 32 reconfiguration delays of
    12.5-400 us; ``max_enumerated_planes=4``, rollout horizon 24).  The
    size-based selection must pick the fused ``lax.scan`` planner and
    the jax timing backend, with the planner's tables on the TPU.  Every
    device plan must be legal, timed by the device as the numpy executor
    times it, and faster than the strawman.  Its decisions and numpy
    re-timed CCTs are compared with the per-step numpy loop's on the
    same cells and the differences printed: on the CPU they are
    bitwise-identical, on a chip whose float64 is emulated they are not.
    Their drift from the reference must stay within what the reference
    itself drifts when its inputs carry noise at the scale of the
    device's rounding (see ``phase_a``).
(f64) float64 arithmetic -- how far the device's add, mul and div stray
    from IEEE, in ulp, and whether it keeps the float64 range: the
    premise of (a)'s bound.  Printed, not checked.
(b) batched timing -- the decisions of (a) timed by
    ``batch_evaluate(backend="jax", attribution=True)`` against the
    numpy backend; CCTs agree within `repro.core.tolerances` and the
    attribution sums to each CCT.
(c) closed-loop replay -- a Qwen2-MoE training trace beside a Qwen2
    1.5B prefill trace at their real payload bytes, replayed through the
    fabric arbiter with the lease re-scoring pinned to jax and then to
    numpy; every job completes and per-job CCTs agree.

Cold times include tracing and compilation; warm times repeat the same
call.  The script runs in one process and starts none.  It exits
non-zero, printing no result, when JAX finds no TPU, and when any check
fails.

Usage:  python chip_smoke.py
"""

import dataclasses
import json
import pathlib
import sys
import time

import numpy as np

_ROOT = pathlib.Path(__file__).resolve().parent
_PLATFORM = "tpu"

_NODES, _PLANES, _SIDE = 128, 8, 32
_ENUM_PLANES, _HORIZON = 4, 24
_TRACE_FABRIC = dict(n_nodes=8, n_planes=4, t_recfg=200e-6)
# Relative input noise of the reference ensemble in (a), and its runs.
# 1e-14 is about 45 ulp: the order of one emulated float64 op's error on
# a TPU (the ``f64_arith`` phase prints it), and the smallest scale that
# does so, since a larger one only widens the bounds.
_NOISE, _NOISE_RUNS = 1e-14, 4


def _emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _grid_cells(side: int, noise_seed: int | None = None):
    """The CHAIN grid; with a seed, every message size and reconfiguration
    delay is scaled by its own ``1 + u``, ``u ~ U(-_NOISE, _NOISE)``."""
    from repro.core import OpticalFabric, pairwise_alltoall

    u = np.zeros((side, side, 2))
    if noise_seed is not None:
        u = np.random.default_rng(noise_seed).uniform(-_NOISE, _NOISE, u.shape)
    patterns: dict = {}
    cells = []
    for i in range(side):
        for j in range(side):
            size = 1e6 * (1 + i) * (1 + u[i, j, 1])
            if size not in patterns:
                patterns[size] = pairwise_alltoall(_NODES, size)
            fabric = OpticalFabric(
                _NODES, _PLANES, t_recfg=12.5e-6 * (1 + j) * (1 + u[i, j, 0])
            )
            cells.append((fabric, patterns[size]))
    return cells


def _rel_diff(a: np.ndarray, b: np.ndarray) -> float:
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-300)))


def _close_both_ways(a: np.ndarray, b: np.ndarray) -> bool:
    from repro.core.tolerances import times_close_arr

    return bool(times_close_arr(a, b).all() and times_close_arr(b, a).all())


def _plane_sets(decisions) -> tuple:
    return tuple(tuple(sorted(s)) for s in decisions.splits), decisions.bypass


class _PathProbe:
    """Records which device path the planner took.

    Wraps the fused planner's table builder (which arrays it built, and
    on which platform) and the jax timing backend (how many batches it
    timed), the same hooks a test would monkeypatch.
    """

    def __init__(self) -> None:
        from repro.core.ir import fused, get_backend

        self.table_platforms: list[set[str]] = []
        self.jax_batches = 0
        build, backend = fused._chain_tables, get_backend("jax")
        derive = backend.derive_timing

        def tables(st, *args):
            tab = build(st, *args)
            self.table_platforms.append(
                {d.platform for a in tab.values() for d in a.devices()}
            )
            return tab

        def timing(packed, attribution=False):
            self.jax_batches += 1
            return derive(packed, attribution=attribution)

        fused._chain_tables = tables
        backend.derive_timing = timing


def phase_a(probe: _PathProbe, side: int = _SIDE):
    """Fused grid planning vs the per-step numpy loop."""
    from repro.core import BatchInstance, batch_evaluate
    from repro.core.api import PlannerOptions, PlanRequest, plan
    from repro.core.baselines import strawman_instance
    from repro.core.ir import (
        select_backend_by_size,
        select_planner_by_size,
        to_ir,
        validate_ir,
    )
    from repro.core.ir.backends import (
        DEFAULT_GRID_BACKEND_THRESHOLD,
        ENV_GRID_BACKEND_THRESHOLD,
    )
    from repro.core.tolerances import times_close_arr

    cells = _grid_cells(side)
    n = len(cells)
    _check(select_planner_by_size(n) == "fused", "fused planner chosen")
    _check(
        select_backend_by_size(
            n, ENV_GRID_BACKEND_THRESHOLD, DEFAULT_GRID_BACKEND_THRESHOLD
        )
        == "jax",
        "jax backend chosen",
    )
    opts = PlannerOptions(
        max_enumerated_planes=_ENUM_PLANES, rollout_horizon=_HORIZON
    )
    request = PlanRequest.grid(cells, options=opts)
    _, cold = _timed(lambda: plan(request))
    dev, warm = _timed(lambda: plan(request))
    _check(len(probe.table_platforms) == 2, "fused planner ran")
    _check(
        probe.table_platforms[-1] == {_PLATFORM},
        f"planner tables on {_PLATFORM}: {probe.table_platforms}",
    )
    _check(probe.jax_batches >= 4, "grid scoring used jax")
    step_opts = dataclasses.replace(opts, planner="step", backend="numpy")
    ref, t_step = _timed(
        lambda: plan(PlanRequest.grid(cells, options=step_opts))
    )
    dev_dec = [c.plan.decisions for c in dev.grid]
    ref_dec = [c.plan.decisions for c in ref.grid]
    mismatches = sum(a != b for a, b in zip(dev_dec, ref_dec))
    # Cells whose planes per step differ, not only the split volumes.
    structural = sum(
        _plane_sets(a) != _plane_sets(b) for a, b in zip(dev_dec, ref_dec)
    )

    def retime(instances):
        return batch_evaluate(instances, backend="numpy").cct

    def retime_plans(cells, decisions):
        return retime(
            [BatchInstance(f, p, d) for (f, p), d in zip(cells, decisions)]
        )

    dev_cct = retime_plans(cells, dev_dec)
    ref_cct = retime_plans(cells, ref_dec)
    straw_cct = retime([strawman_instance(f, p) for f, p in cells])
    for c in dev.grid:
        validate_ir(to_ir(c.plan.schedule()))
    _check(
        _close_both_ways(np.array(dev.ccts), dev_cct),
        "device CCTs match the numpy executor on the device's plans",
    )
    _check(
        bool((dev_cct < straw_cct).all()), "device plans beat the strawman"
    )

    # Plan quality.  The per-step loop's choices hinge on rounding between
    # candidates that tie mathematically, so no arithmetic but IEEE
    # float64 reproduces them cell for cell (ROADMAP speed item 3).  The
    # bound on the device's drift from the reference is what the
    # reference itself drifts when fed inputs that differ at the scale of
    # one emulated op's error: `_NOISE_RUNS` runs of the numpy loop on the
    # grid with noisy inputs (`_grid_cells`), each plan timed on its own
    # cell.  The device may be no worse than the worst cell the noisy
    # runs produced, and may have at most twice the largest of their
    # worse-cell counts and of their mean relative drifts.
    noisy = [_grid_cells(side, noise_seed=s) for s in range(_NOISE_RUNS)]
    noisy_cells = [c for grid in noisy for c in grid]
    noisy_plan, t_noisy = _timed(
        lambda: plan(PlanRequest.grid(noisy_cells, options=step_opts))
    )
    noisy_cct = retime_plans(
        noisy_cells, [c.plan.decisions for c in noisy_plan.grid]
    ).reshape(_NOISE_RUNS, n)

    def worse_cells(cct):
        return ~times_close_arr(cct, ref_cct)

    def drift(cct):
        rel = (cct - ref_cct) / ref_cct
        return float(rel.max()), int(worse_cells(cct).sum()), float(rel.mean())

    dev_drift = drift(dev_cct)
    # Device-worse cells that no noisy run moved: tie flips the noise
    # did not reach.  Printed, not checked.
    unmatched = worse_cells(dev_cct) & ~np.any(
        [worse_cells(c) for c in noisy_cct], axis=0
    )
    noisy_drift = [drift(c) for c in noisy_cct]
    bound = (
        max(d[0] for d in noisy_drift),
        2 * max(d[1] for d in noisy_drift),
        2 * max(d[2] for d in noisy_drift),
    )
    _emit(
        phase="a_fused_grid_plan",
        cells=n,
        cold_s=cold,
        warm_s=warm,
        per_step_numpy_s=t_step,
        jax_batches=probe.jax_batches,
        decision_mismatch_cells=mismatches,
        bitwise_identical_decisions=mismatches == 0,
        plane_choice_mismatch_cells=structural,
        cells_worse_than_numpy=dev_drift[1],
        cells_better_than_numpy=int(
            (~times_close_arr(ref_cct, dev_cct)).sum()
        ),
        max_rel_cct_diff_numpy_retimed=_rel_diff(dev_cct, ref_cct),
        max_rel_cct_worse=dev_drift[0],
        mean_rel_cct_diff_numpy_retimed=dev_drift[2],
        cells_worse_not_moved_by_noise=int(unmatched.sum()),
        noisy_reference_s=t_noisy,
        noisy_reference=[
            dict(max_rel_cct_worse=d[0], cells_worse=d[1], mean_rel=d[2])
            for d in noisy_drift
        ],
        bound=dict(
            max_rel_cct_worse=bound[0],
            cells_worse=bound[1],
            mean_rel=bound[2],
        ),
    )
    _check(
        dev_drift[0] <= bound[0],
        f"no device plan worse than the noisy reference's worst "
        f"({dev_drift[0]} > {bound[0]})",
    )
    _check(
        dev_drift[1] <= bound[1],
        f"cells worse than the reference ({dev_drift[1]} > {bound[1]})",
    )
    _check(
        dev_drift[2] <= bound[2],
        f"mean drift from the reference ({dev_drift[2]} > {bound[2]})",
    )
    return cells, dev_dec


def phase_f64():
    """The device's float64 add, mul and div against IEEE (numpy)."""
    import jax
    import jax.numpy as jnp
    from repro.core.ir import x64

    rng = np.random.default_rng(0)
    # Operands of the planner's own magnitudes: seconds, bytes/s, bytes.
    t = rng.uniform(1e-6, 1e-2, 1 << 16)
    rate = rng.uniform(0.5e9, 3e10, 1 << 16)
    ops = {
        "add": (jnp.add, np.add, t, t[::-1].copy()),
        "mul": (jnp.multiply, np.multiply, t, rate),
        "div": (jnp.divide, np.divide, rate * t, rate),
    }
    out = {}
    with x64():
        for name, (dev_op, np_op, x, y) in ops.items():
            got = np.asarray(jax.jit(dev_op)(jnp.asarray(x), jnp.asarray(y)))
            want = np_op(x, y)
            ulp = np.abs(got - want) / np.spacing(np.abs(want))
            out[name] = dict(
                lanes_off_ieee=int((got != want).sum()),
                max_ulp=float(ulp.max()),
            )
        big = jnp.asarray([1e300, 3.5e38])
        big = np.asarray(jax.jit(lambda v: v * 1.0)(big))
    _emit(
        phase="f64_arith",
        lanes=int(t.size),
        ops=out,
        keeps_f64_range=bool(np.isfinite(big).all()),
    )


def phase_b(cells, decisions):
    """Device batched timing with attribution vs the numpy backend."""
    from repro.core import BatchInstance, batch_evaluate

    instances = [
        BatchInstance(f, p, d) for (f, p), d in zip(cells, decisions)
    ]

    def run(backend):
        return batch_evaluate(instances, backend=backend, attribution=True)

    _, cold = _timed(lambda: run("jax"))
    got, warm = _timed(lambda: run("jax"))
    ref, t_numpy = _timed(lambda: run("numpy"))
    _check(_close_both_ways(got.cct, ref.cct), "jax CCTs match numpy")
    _check(
        np.array_equal(got.n_reconfigurations, ref.n_reconfigurations),
        "reconfiguration counts match numpy",
    )
    _check(bool(got.feasible.all() and got.volume_ok.all()), "plans feasible")
    att = got.attribution
    total = np.where(att.plane_mask, att.plane_total, 0.0)
    want = np.where(att.plane_mask, got.cct[:, None], 0.0)
    _check(np.array_equal(total, want), "attribution sums to the CCT")
    _emit(
        phase="b_batched_timing",
        cells=len(instances),
        cold_s=cold,
        warm_s=warm,
        numpy_s=t_numpy,
        max_rel_cct_diff=_rel_diff(got.cct, ref.cct),
        bitwise_identical_cct=bool(np.array_equal(got.cct, ref.cct)),
    )


def phase_c(probe: _PathProbe):
    """Closed-loop model-trace replay, re-scoring on jax vs numpy."""
    from repro.core import OpticalFabric
    from repro.runtime import replay
    from repro.trace import static_trace
    from repro.trace.replay import trace_to_jobs

    traces = [
        static_trace("qwen2_moe_a2_7b", kind="train", dp=2, tp=4, n_steps=2),
        static_trace("qwen2_1_5b", kind="prefill", dp=2, tp=4, n_steps=2),
    ]
    fabric = OpticalFabric(**_TRACE_FABRIC)
    jobs = trace_to_jobs(traces, fabric, size_scale=1.0)

    def run(backend):
        return replay(
            jobs, fabric, method="greedy", solo_refs=False, backend=backend
        )

    before = probe.jax_batches
    _, cold = _timed(lambda: run("jax"))
    device_batches = probe.jax_batches - before
    got, warm = _timed(lambda: run("jax"))
    ref, t_numpy = _timed(lambda: run("numpy"))
    _check(device_batches > 0, "re-scoring batches reached the device")
    for report in (got, ref):
        _check(
            len(report.completed) == len(report.records) == len(jobs),
            "every job completed",
        )
    got_cct = np.array([r.cct for r in got.records])
    ref_cct = np.array([r.cct for r in ref.records])
    _check(_close_both_ways(got_cct, ref_cct), "per-job CCTs match numpy")
    _emit(
        phase="c_trace_replay",
        jobs=len(jobs),
        cold_s=cold,
        warm_s=warm,
        numpy_s=t_numpy,
        device_rescoring_batches=device_batches,
        max_rel_cct_diff=_rel_diff(got_cct, ref_cct),
        bitwise_identical_cct=bool(np.array_equal(got_cct, ref_cct)),
    )


def main() -> int:
    import jax

    devices = jax.devices()
    if devices[0].platform != _PLATFORM:
        print(
            f"chip_smoke: JAX found no TPU (platform "
            f"{devices[0].platform!r}); nothing was run",
            file=sys.stderr,
        )
        return 1
    sys.path.insert(0, str(_ROOT / "src"))
    from repro import use_compile_cache

    _emit(phase="setup", compile_cache=use_compile_cache())
    probe = _PathProbe()
    phase_f64()
    cells, decisions = phase_a(probe)
    phase_b(cells, decisions)
    phase_c(probe)
    _emit(
        ok=True,
        device={
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
