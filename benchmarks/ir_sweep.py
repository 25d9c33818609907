"""Batched scenario-sweep benchmark: array IR vs per-instance object path.

Two sweeps, two acceptance gates:

* ``run`` -- the historical 64-instance sweep (8 message sizes x 8
  reconfiguration delays of strawman-ICR Rabenseifner AllReduce on
  8 nodes x 4 planes), evaluated per instance through the *historical*
  object pipeline (`repro.core.simulator.execute` building
  ``PlaneActivity`` objects, validated with the interpreted
  ``validate_object`` oracle) and in ONE `repro.core.ir.batch_evaluate`
  pass.  Per-instance CCTs must agree within 1e-9 and the batched pass
  must be >= 5x faster (gated for the default numpy backend; pass
  ``--backend jax|pallas`` to time an accelerator backend instead --
  parity still asserted).
* ``backend_throughput`` -- the LARGE grid (32 sizes x 32 delays of
  128-node pairwise all-to-all, 127 steps): one packed batch evaluated by
  every available timing backend, with cold (first call: trace+compile)
  and warm timings reported separately (``compile_ms`` is an ungated
  wall-clock row; the gate only sees warm numbers).  The jax backend
  must be >= 2x faster than the numpy reference on this grid (CPU jit
  counts); the Pallas backend runs in interpret mode for functional
  parity only (its wall time on CPU is the interpreter's, not the
  kernel's) -- a compiled-mode (``interpret=False``) probe runs once.
  On the CPU its refusal is recorded in the payload; on an accelerator
  a failure to compile fails the run.
  ``run.py`` dumps these numbers to ``BENCH_backends.json`` for the
  cross-PR perf trajectory.

A fifth gate, ``fused_grid``, times the fused on-device CHAIN planner
(`repro.core.ir.fused`: the whole greedy loop as ONE jitted
``lax.scan``) against the per-step numpy loop on the same 1024-cell
grid (``max_enumerated_planes=4`` so the reserve sets are the dynamic
soonest-free rows, the at-scale configuration).  The fused warm time
must be >= 2x faster with bitwise-identical chosen splits (0 mismatched
cells, asserted in-run).  Cold (trace+compile) time is reported
ungated.

A third gate rides along: ``independent_grid`` plans a 16 x 16 grid of
64-node pairwise all-to-all cells with the instance-batched
INDEPENDENT-mode greedy (``swot_greedy_grid(mode=INDEPENDENT)``) and
must be >= 2x faster than the per-instance ``independent_decisions``
loop -- with bitwise-identical decisions.  Its numbers land in both
``BENCH_sweep.json`` (as ``run`` rows) and ``BENCH_backends.json``.

A fourth section, ``bypass_sweep``, gates Topology Bypassing: the
bypass-enabled grid greedy (``swot_greedy_grid(bypass_depth=2)``) must
STRICTLY reduce CCT vs the no-bypass greedy at the documented
high-``t_recfg`` point (pre-staged 8-node pairwise all-to-all on 4
planes, ``t_recfg`` = 3.2 ms), every bypass schedule must pass
``validate_ir``, and grid CCTs must match the object executor bitwise.
The per-point CCTs and bypass/no-bypass ratios are deterministic
``BENCH_sweep.json`` rows, so the regression gate pins the reduction.
"""

import argparse
import time

import numpy as np

from repro.core import (
    BatchInstance,
    OpticalFabric,
    batch_evaluate,
    independent_decisions,
    pairwise_alltoall,
    rabenseifner_allreduce,
    strawman_instance,
    swot_greedy_grid,
)
from repro.core.ir import BackendUnavailable, get_backend, resolve_backend
from repro.core.ir.engine import pack_instances
from repro.core.schedule import DependencyMode, Kind, validate_object
from repro.core.simulator import execute
from repro.obs import attribute


def _object_path_cct(inst: BatchInstance) -> float:
    """The pre-IR per-instance pipeline: build objects, validate, read CCT."""
    schedule = execute(
        inst.fabric, inst.pattern, inst.decisions, validate=False
    )
    validate_object(schedule)
    return schedule.cct

_N_NODES = 8
_N_PLANES = 4
_SIZES = tuple(2**i * 1e6 for i in range(8))  # 1 .. 128 MB
_RECFGS = tuple(25e-6 * 2**i for i in range(8))  # 25 us .. 3.2 ms


def _instances() -> list[BatchInstance]:
    return [
        strawman_instance(
            OpticalFabric(_N_NODES, _N_PLANES, t_recfg=t_recfg),
            rabenseifner_allreduce(_N_NODES, size),
            prestage=True,
        )
        for size in _SIZES
        for t_recfg in _RECFGS
    ]


def run(
    quick: bool = False, backend: str | None = None
) -> list[tuple[str, float, str]]:
    del quick  # the 64-cell sweep IS the CI smoke test
    # Resolve now so the row tag and the numpy-only gate reflect what is
    # actually timed (backend=None follows REPRO_IR_BACKEND).
    backend = resolve_backend(backend).name
    instances = _instances()
    n = len(instances)
    # Best-of-3 on both sides: one-shot timings are too noisy for a CI
    # gate (first-call numpy warm-up, scheduler jitter).
    t_object = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        object_cct = np.array([_object_path_cct(i) for i in instances])
        t_object = min(t_object, time.perf_counter() - t0)
    batch_evaluate(instances, backend=backend)  # warm (jit compiles here)
    t_batch = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        result = batch_evaluate(instances, backend=backend)
        t_batch = min(t_batch, time.perf_counter() - t0)
    err = float(np.max(np.abs(result.cct - object_cct)))
    assert err <= 1e-9, f"batched CCT diverges from object path by {err}"
    speedup = t_object / t_batch
    # The >= 5x gate pins the refactor payoff for the deterministic
    # default; accelerator backends are gated on the large grid instead
    # (64 cells cannot amortize a device round trip).
    if backend == "numpy":
        assert speedup >= 5.0, (
            f"batched IR sweep only {speedup:.1f}x faster than the "
            "per-instance object path (acceptance gate is >= 5x)"
        )
    tag = backend
    return [
        (
            "ir_sweep_object_path",
            t_object * 1e6 / n,
            f"{n} instances total={t_object * 1e3:.1f}ms",
        ),
        (
            f"ir_sweep_batched_{tag}",
            t_batch * 1e6 / n,
            f"speedup={speedup:.1f}x max_cct_err={err:.1e}",
        ),
    ] + independent_grid_rows() + bypass_rows() + attribution_rows()


# INDEPENDENT-mode grid: 16 sizes x 16 delays of 64-node pairwise
# all-to-all (63 steps each).  Deep enough in steps that the
# per-instance argmin-packing loop's Python turns dominate, small
# enough (~0.2 s per rep) for the CI smoke sweep.
_INDEP_NODES = 64
_INDEP_PLANES = 8
_INDEP_SIZES = tuple(1e6 * (1 + i) for i in range(16))
_INDEP_RECFGS = tuple(25e-6 * (1 + i) for i in range(16))

_independent_grid_cache: dict | None = None


def independent_grid(quick: bool = False) -> dict:
    """Instance-batched INDEPENDENT grid vs the per-instance loop.

    Both sides produce scored plans for every cell: the per-instance
    path runs ``independent_decisions`` per cell plus one
    ``batch_evaluate`` scoring pass; the batched path is ONE
    ``swot_greedy_grid(mode=INDEPENDENT)`` call.  Decisions must be
    bitwise identical and the batched path >= 2x faster (the
    acceptance gate for batching the last per-step Python out of the
    grid path).  The payload is memoized so ``run.py`` can record it
    in both BENCH JSON files without re-timing.
    """
    global _independent_grid_cache
    del quick  # the grid must stay step-deep or the gate is meaningless
    if _independent_grid_cache is not None:
        return _independent_grid_cache
    patterns = {
        size: pairwise_alltoall(_INDEP_NODES, size)
        for size in _INDEP_SIZES
    }
    cells = [
        (
            OpticalFabric(_INDEP_NODES, _INDEP_PLANES, t_recfg=t_recfg),
            patterns[size],
        )
        for size in _INDEP_SIZES
        for t_recfg in _INDEP_RECFGS
    ]
    t_instance = t_grid = float("inf")
    # Interleave best-of-3 reps so host load spikes skew both sides alike.
    for _ in range(3):
        t0 = time.perf_counter()
        decisions = [
            independent_decisions(fabric, pattern)
            for fabric, pattern in cells
        ]
        batch_evaluate(
            [
                BatchInstance(fabric, pattern, dec)
                for (fabric, pattern), dec in zip(cells, decisions)
            ]
        )
        t_instance = min(t_instance, time.perf_counter() - t0)
        t0 = time.perf_counter()
        plans = swot_greedy_grid(cells, mode=DependencyMode.INDEPENDENT)
        t_grid = min(t_grid, time.perf_counter() - t0)
    mismatches = sum(
        plan.decisions != dec for plan, dec in zip(plans, decisions)
    )
    assert mismatches == 0, (
        f"INDEPENDENT grid decisions diverge from per-instance "
        f"independent_decisions on {mismatches}/{len(cells)} cells"
    )
    speedup = t_instance / t_grid
    assert speedup >= 2.0, (
        f"INDEPENDENT grid greedy only {speedup:.1f}x faster than the "
        "per-instance path (acceptance gate is >= 2x)"
    )
    _independent_grid_cache = {
        "cells": len(cells),
        "pattern": f"pairwise_alltoall_{_INDEP_NODES}",
        "n_steps": cells[0][1].n_steps,
        "n_planes": _INDEP_PLANES,
        "per_instance_ms": round(t_instance * 1e3, 3),
        "grid_ms": round(t_grid * 1e3, 3),
        "us_per_instance": round(t_grid * 1e6 / len(cells), 3),
        "speedup_vs_per_instance": round(speedup, 2),
        "decision_mismatches": mismatches,
    }
    return _independent_grid_cache


def independent_grid_rows(
    quick: bool = False,
) -> list[tuple[str, float, str]]:
    """``independent_grid`` reshaped into benchmark CSV rows."""
    g = independent_grid(quick=quick)
    return [
        (
            "indep_grid_per_instance",
            g["per_instance_ms"] * 1e3 / g["cells"],
            f"{g['cells']} cells total={g['per_instance_ms']:.1f}ms",
        ),
        (
            "indep_grid_batched",
            g["us_per_instance"],
            f"speedup={g['speedup_vs_per_instance']}x "
            f"mismatches={g['decision_mismatches']}",
        ),
    ]


# Topology Bypassing sweep: pre-staged 8-node pairwise all-to-all on 4
# planes (rotation configs, so the pre-staged rot(1) circuit self-relays
# to rot(2) in 2 hops) across the t_recfg axis.  In the high-t_recfg
# regime relays dominate reconfiguration; the documented 3.2 ms point
# must show a strict >= 25% CCT reduction (observed ~47%).
_BYPASS_NODES = 8
_BYPASS_PLANES = 4
_BYPASS_SIZE = 8e6
_BYPASS_RECFGS = (2e-4, 8e-4, 3.2e-3)
_BYPASS_DEPTH = 2
_BYPASS_GATE_RECFG = 3.2e-3
_BYPASS_GATE_REDUCTION = 0.25


def bypass_sweep(quick: bool = False) -> list[tuple[str, float, str]]:
    """Bypass-enabled vs no-bypass grid greedy on the t_recfg axis.

    Deterministic CCT rows (simulated quantities -- identical on any
    machine, so the regression gate holds them to the 25% band) plus the
    bypass/no-bypass CCT ratio per point.  Asserts in-run: every bypass
    schedule passes ``validate_ir`` with object-path-bitwise CCT, bypass
    never loses (the guarded pick), and the documented high-t_recfg
    point strictly reduces CCT by the gate margin.
    """
    del quick  # 3 cells; the sweep IS the CI smoke test
    pattern = pairwise_alltoall(_BYPASS_NODES, _BYPASS_SIZE)
    cells = []
    for t_recfg in _BYPASS_RECFGS:
        fabric = OpticalFabric(
            _BYPASS_NODES, _BYPASS_PLANES, t_recfg=t_recfg
        ).prestaged(pattern.steps[0].config)
        cells.append((fabric, pattern))
    base = swot_greedy_grid(cells, backend="numpy")
    byp = swot_greedy_grid(
        cells, backend="numpy", bypass_depth=_BYPASS_DEPTH
    )
    # Every available accelerator backend must reproduce the numpy CCTs
    # bitwise on this bypass batch (relay routes + fractional-bandwidth
    # splits): the pallas kernel handles bypass natively now, so this
    # in-run check keeps the no-numpy-delegation contract measured, not
    # assumed.
    byp_insts = [
        BatchInstance(fabric, pattern, y.decisions)
        for (fabric, pattern), y in zip(cells, byp)
    ]
    ref = batch_evaluate(byp_insts, backend="numpy")
    for name in ("jax", "pallas"):
        try:
            get_backend(name)
        except BackendUnavailable:
            continue
        got = batch_evaluate(byp_insts, backend=name)
        assert np.array_equal(got.cct, ref.cct), (
            f"{name} backend CCT diverges from numpy on bypass batch"
        )
    rows = []
    for (fabric, _), b, y in zip(cells, base, byp):
        # Legality + object-path parity for every bypass schedule.
        schedule = y.schedule()  # execute() validates (P1-P4)
        assert schedule.cct == y.cct, "IR/object CCT parity broken"
        assert y.cct <= b.cct + 1e-12, "guarded bypass pick regressed CCT"
        t_us = fabric.t_recfg * 1e6
        label = f"bypass_pairwise{_BYPASS_NODES}x{_BYPASS_PLANES}"
        rows.append(
            (
                f"{label}_t{t_us:.0f}_nobypass_cct",
                b.cct * 1e6,
                f"t_recfg={t_us:.0f}us",
            )
        )
        rows.append(
            (
                f"{label}_t{t_us:.0f}_depth{_BYPASS_DEPTH}_cct",
                y.cct * 1e6,
                f"reduction={1 - y.cct / b.cct:.1%}",
            )
        )
        rows.append(
            (
                f"{label}_t{t_us:.0f}_cct_ratio",
                y.cct / b.cct,
                "bypass/no-bypass (<= 1 by the guarded pick)",
            )
        )
        if fabric.t_recfg == _BYPASS_GATE_RECFG:
            assert y.cct < b.cct * (1.0 - _BYPASS_GATE_REDUCTION), (
                f"bypass reduction only {1 - y.cct / b.cct:.1%} at "
                f"t_recfg={t_us:.0f}us (acceptance gate is "
                f">= {_BYPASS_GATE_REDUCTION:.0%} strict)"
            )
            n_relays = sum(
                1 for a in schedule.activities if a.route >= 0
            )
            assert n_relays > 0, "gate point used no relays"
            # Bypass hit rate: of the steps that needed a circuit
            # change, the fraction served by relaying over installed
            # circuits instead of reconfiguring.  Deterministic and
            # gated HIGHER-is-better by check_regression.
            relay_steps = {
                a.step for a in schedule.activities if a.route >= 0
            }
            recfg_steps = {
                a.step
                for a in schedule.activities
                if a.kind is Kind.RECFG
            }
            denom = len(relay_steps | recfg_steps)
            rows.append(
                (
                    f"{label}_t{t_us:.0f}_bypass_hit_rate",
                    len(relay_steps) / denom if denom else 0.0,
                    f"{len(relay_steps)} relay vs {len(recfg_steps)} "
                    "reconfig steps",
                )
            )
    return rows


# Back-compat friendly alias used by ``run``.
bypass_rows = bypass_sweep


# CCT-attribution sweep: overlap efficiency of the greedy plans across
# the t_recfg axis for the two headline algorithms.  Simulated
# quantities (deterministic on any machine), gated HIGHER-is-better by
# check_regression: an overlap-efficiency drop past the band means a
# scheduler change stopped hiding reconfigurations it used to hide.
_ATTR_NODES = 8
_ATTR_PLANES = 4
_ATTR_SIZE = 8e6
_ATTR_RECFGS = (50e-6, 200e-6, 3.2e-3)
_ATTR_ALGS = (
    ("rab", rabenseifner_allreduce),
    ("pw", pairwise_alltoall),
)


def attribution_rows(quick: bool = False) -> list[tuple[str, float, str]]:
    """Overlap-efficiency rows from attributed greedy plans.

    One ``swot_greedy_grid`` pass plans every cell; every available
    timing backend then re-evaluates the batch with
    ``attribution=True``.  In-run gates: components must sum *bitwise*
    to the CCT on every backend, efficiencies must agree across
    backends within 1e-9, and the object-walk oracle
    (``repro.obs.attribute`` over ``execute``) must agree per cell.
    """
    del quick  # 6 cells; the sweep IS the CI smoke test
    cells = []
    labels = []
    for tag, make in _ATTR_ALGS:
        pattern = make(_ATTR_NODES, _ATTR_SIZE)
        for t_recfg in _ATTR_RECFGS:
            fabric = OpticalFabric(
                _ATTR_NODES, _ATTR_PLANES, t_recfg=t_recfg
            ).prestaged(pattern.steps[0].config)
            cells.append((fabric, pattern))
            labels.append(
                f"attr_{tag}{_ATTR_NODES}x{_ATTR_PLANES}"
                f"_t{t_recfg * 1e6:.0f}_overlap_eff"
            )
    plans = swot_greedy_grid(cells, backend="numpy")
    instances = [
        BatchInstance(p.fabric, p.pattern, p.decisions) for p in plans
    ]
    eff = hidden = exposed = None
    for name in ("numpy", "jax", "pallas"):
        try:
            get_backend(name)
        except BackendUnavailable:
            continue
        result = batch_evaluate(instances, backend=name, attribution=True)
        att = result.attribution
        total = np.where(att.plane_mask, att.plane_total, 0.0)
        want = np.where(att.plane_mask, result.cct[:, None], 0.0)
        assert np.array_equal(total, want), (
            f"{name} attribution components do not sum bitwise to CCT"
        )
        if eff is None:
            eff = att.overlap_efficiency
            hidden, exposed = att.hidden_recfg, att.exposed_recfg
        else:
            err = float(np.max(np.abs(att.overlap_efficiency - eff)))
            assert err <= 1e-9, (
                f"{name} overlap efficiency diverges from numpy by {err}"
            )
    assert eff is not None
    rows = []
    for label, inst, plan, e, h, x in zip(
        labels, instances, plans, eff, hidden, exposed
    ):
        # Object-walk oracle parity per cell.
        schedule = execute(
            inst.fabric, inst.pattern, inst.decisions, validate=False
        )
        oracle = attribute(schedule)
        o_eff = float(oracle.overlap_efficiency)
        assert abs(o_eff - float(e)) <= 1e-9, (
            f"{label}: object-walk efficiency {o_eff} vs batched {e}"
        )
        rows.append(
            (
                label,
                float(e),
                f"hidden={float(h) * 1e6:.1f}us "
                f"exposed={float(x) * 1e6:.1f}us "
                f"cct={plan.cct * 1e6:.1f}us",
            )
        )
    return rows


# Large grid: 32 sizes x 32 delays of 128-node pairwise all-to-all
# (127 steps) = 1024 cells.  Deep enough in steps that the numpy path's
# per-step Python turns dominate while the jax scan stays one compiled
# program (~3.2x observed unloaded, higher under CPU contention, vs the
# 2x gate); small enough to build in a few seconds.
_GRID_NODES = 128
_GRID_PLANES = 8
_GRID_SIZES = tuple(1e6 * (1 + i) for i in range(32))
_GRID_RECFGS = tuple(12.5e-6 * (1 + i) for i in range(32))


def backend_throughput(quick: bool = False) -> dict:
    """Time every available backend on one packed large-grid batch.

    Returns a JSON-ready payload (``run.py`` writes it to
    ``BENCH_backends.json``); asserts the jax backend is >= 2x the numpy
    reference on this grid whenever jax is importable.  The first call
    per backend is timed separately as ``cold_ms`` (trace + jit compile
    + first run) and ``compile_ms`` (cold minus warm best) -- ungated
    wall-clock rows, so compile latency is tracked without contaminating
    the warm-throughput gate.
    """
    del quick  # the grid must stay large or the 2x gate is meaningless
    instances = [
        strawman_instance(
            OpticalFabric(_GRID_NODES, _GRID_PLANES, t_recfg=t_recfg),
            pairwise_alltoall(_GRID_NODES, size),
            prestage=True,
        )
        for size in _GRID_SIZES
        for t_recfg in _GRID_RECFGS
    ]
    packed = pack_instances(instances, None)
    ref_cct: np.ndarray | None = None
    payload: dict = {
        "grid": {
            "cells": len(instances),
            "pattern": f"pairwise_alltoall_{_GRID_NODES}",
            "n_steps": instances[0].pattern.n_steps,
            "n_planes": _GRID_PLANES,
        },
        "backends": {},
    }
    engines = {}
    for name in ("numpy", "jax", "pallas"):
        try:
            engines[name] = get_backend(name)
        except BackendUnavailable as exc:
            payload["backends"][name] = {"unavailable": str(exc)}
    best = {name: float("inf") for name in engines}
    cold = {}
    results = {}
    for name, engine in engines.items():
        # Cold = trace + compile + first execution (numpy's is just its
        # first-touch warm-up; still reported for symmetry).
        t0 = time.perf_counter()
        results[name] = engine.derive_timing(packed)
        cold[name] = time.perf_counter() - t0
    # Interleave the timed reps across backends so a load spike on the
    # host (CI runners are shared) skews every backend alike instead of
    # flipping the gated ratio.
    for rep in range(5):
        for name, engine in engines.items():
            if name == "pallas" and rep >= 2:
                continue  # interpret mode is slow; 2 reps suffice
            t0 = time.perf_counter()
            results[name] = engine.derive_timing(packed)
            best[name] = min(best[name], time.perf_counter() - t0)
    for name in engines:
        result = results[name]
        if ref_cct is None:
            ref_cct = result.cct
            err = 0.0
        else:
            err = float(np.max(np.abs(result.cct - ref_cct)))
            assert err <= 1e-9, (
                f"{name} backend CCT diverges from numpy by {err}"
            )
        payload["backends"][name] = {
            "ms": round(best[name] * 1e3, 3),
            "cold_ms": round(cold[name] * 1e3, 3),
            "compile_ms": round(
                max(0.0, cold[name] - best[name]) * 1e3, 3
            ),
            "us_per_instance": round(
                best[name] * 1e6 / len(instances), 3
            ),
            "max_cct_err_vs_numpy": err,
        }
    np_ms = payload["backends"]["numpy"]["ms"]
    for name, entry in payload["backends"].items():
        if "ms" in entry:
            entry["speedup_vs_numpy"] = round(np_ms / entry["ms"], 2)
    jax_entry = payload["backends"]["jax"]
    if "ms" in jax_entry:
        assert jax_entry["speedup_vs_numpy"] >= 2.0, (
            f"jax backend only {jax_entry['speedup_vs_numpy']}x vs numpy "
            "on the large grid (acceptance gate is >= 2x)"
        )
    # Compiled-pallas probe: interpret=False compiles the actual Mosaic/
    # Triton kernel, which needs a TPU/GPU backend (see the probe).
    payload["pallas_compiled"] = _pallas_compiled_probe()
    # The INDEPENDENT-mode grid gate rides along in the same payload so
    # BENCH_backends.json tracks both batching trajectories per PR,
    # as does the fused on-device planner gate.
    payload["independent_grid"] = independent_grid()
    payload["fused_grid"] = fused_grid()
    return payload


def _pallas_compiled_probe() -> dict:
    """Try the pallas kernel with ``interpret=False`` on a small batch.

    On the CPU, Pallas refuses anything but interpret mode; that refusal
    is recorded as data.  On an accelerator any failure to lower,
    compile or run propagates and fails the benchmark.
    """
    import jax

    from repro.core.ir.backends import PallasBackend

    probe = [
        strawman_instance(
            OpticalFabric(8, 4, t_recfg=25e-6),
            pairwise_alltoall(8, 1e6),
            prestage=True,
        )
    ]
    try:
        backend = PallasBackend(interpret=False)
    except BackendUnavailable as exc:
        return {"available": False, "error": str(exc)}
    packed = pack_instances(probe, None)
    try:
        backend.derive_timing(packed)  # compile + run
    except ValueError as exc:
        if jax.default_backend() != "cpu" or "interpret mode" not in str(exc):
            raise
        return {"available": False, "error": f"{type(exc).__name__}: {exc}"}
    t0 = time.perf_counter()
    result = backend.derive_timing(packed)
    warm_ms = (time.perf_counter() - t0) * 1e3
    ref = get_backend("numpy").derive_timing(pack_instances(probe, None))
    err = float(np.max(np.abs(result.cct - ref.cct)))
    return {
        "available": True,
        "warm_ms": round(warm_ms, 3),
        "max_cct_err_vs_numpy": err,
    }


# Fused-planner gate: same 1024-cell grid as ``backend_throughput`` but
# timing the CHAIN *planner* loops themselves (candidate construction,
# water-fill, rollout, selection) rather than the timing recurrence.
# ``max_enumerated_planes=4`` keeps every cell on the dynamic
# soonest-free reserve rows -- the at-scale configuration, and the one
# where the per-step loop's per-step Python cost is honest (8 planes
# enumerated would mean 247 static rows per cell and minutes per rep).
_FUSED_ENUM_PLANES = 4
_FUSED_HORIZON = 24

_fused_grid_cache: dict | None = None


def fused_grid(quick: bool = False) -> dict:
    """Fused ``lax.scan`` CHAIN planner vs the per-step numpy loop.

    Both sides plan the identical 1024-cell grid from identical fresh
    ``_GridState``s (state build excluded from both timings -- it is
    shared setup, not planner work).  Asserts in-run: the fused
    planner's chosen splits are bitwise-identical to the per-step
    loop's on every cell (0 mismatches), and the fused *warm* time
    beats the per-step loop by >= 2x (the perf-optimization acceptance
    gate).  Cold time (trace + XLA compile + first run) is reported
    ungated.  Memoized so ``run.py`` records it without re-timing.
    """
    global _fused_grid_cache
    del quick  # the grid must stay step-deep or the gate is meaningless
    if _fused_grid_cache is not None:
        return _fused_grid_cache
    from repro.core import greedy as _greedy
    from repro.core.ir.fused import fused_chain_grid_chosen

    patterns = {
        size: pairwise_alltoall(_GRID_NODES, size) for size in _GRID_SIZES
    }
    cells = [
        (
            OpticalFabric(_GRID_NODES, _GRID_PLANES, t_recfg=t_recfg),
            patterns[size],
        )
        for size in _GRID_SIZES
        for t_recfg in _GRID_RECFGS
    ]

    def mk_state() -> "_greedy._GridState":
        return _greedy._GridState(
            cells,
            mode=DependencyMode.CHAIN,
            max_enumerated_planes=_FUSED_ENUM_PLANES,
        )

    # Planners mutate their state, so each timed run gets a fresh one.
    # Cold first: the one-time trace+compile of the scan.
    st = mk_state()
    t0 = time.perf_counter()
    fused_chosen = fused_chain_grid_chosen(st, _FUSED_HORIZON)
    t_cold = time.perf_counter() - t0
    t_fused = float("inf")
    for _ in range(2):
        st = mk_state()
        t0 = time.perf_counter()
        fused_chosen = fused_chain_grid_chosen(st, _FUSED_HORIZON)
        t_fused = min(t_fused, time.perf_counter() - t0)
    st = mk_state()
    t0 = time.perf_counter()
    step_chosen = _greedy._chain_grid_chosen(st, _FUSED_HORIZON)
    t_step = time.perf_counter() - t0
    # Decisions parity, cell-resolution: a mismatched cell is one whose
    # chosen split or bypass-hop row differs at any step.
    assert len(step_chosen) == len(fused_chosen), "planner step counts"
    bad_cells: set[int] = set()
    for (rows_s, split_s, byp_s), (rows_f, split_f, byp_f) in zip(
        step_chosen, fused_chosen
    ):
        assert np.array_equal(rows_s, rows_f), "live-row sets diverge"
        bad = (split_s != split_f).any(axis=1) | (byp_s != byp_f).any(
            axis=1
        )
        bad_cells.update(int(c) for c in rows_s[bad])
    mismatches = len(bad_cells)
    assert mismatches == 0, (
        f"fused planner decisions diverge from the per-step loop on "
        f"{mismatches}/{len(cells)} cells"
    )
    speedup = t_step / t_fused
    assert speedup >= 2.0, (
        f"fused planner only {speedup:.1f}x faster than the per-step "
        "loop on the large grid (acceptance gate is >= 2x warm)"
    )
    _fused_grid_cache = {
        "cells": len(cells),
        "pattern": f"pairwise_alltoall_{_GRID_NODES}",
        "n_steps": cells[0][1].n_steps,
        "n_planes": _GRID_PLANES,
        "max_enumerated_planes": _FUSED_ENUM_PLANES,
        "rollout_horizon": _FUSED_HORIZON,
        "per_step_ms": round(t_step * 1e3, 3),
        "fused_cold_ms": round(t_cold * 1e3, 3),
        "fused_warm_ms": round(t_fused * 1e3, 3),
        "us_per_cell": round(t_fused * 1e6 / len(cells), 3),
        "speedup_vs_per_step": round(speedup, 2),
        "decision_mismatches": mismatches,
    }
    return _fused_grid_cache


def backend_rows(quick: bool = False) -> list[tuple[str, float, str]]:
    """``backend_throughput`` reshaped into benchmark CSV rows.

    All row names carry a wall-clock prefix (``ir_backend_`` /
    ``fused_grid_``) so ``check_regression`` excludes the absolute
    microseconds; only the payload's speedup *ratios* are gated.
    """
    payload = backend_throughput(quick=quick)
    cells = payload["grid"]["cells"]
    rows = []
    for name, entry in payload["backends"].items():
        if "ms" not in entry:
            rows.append((f"ir_backend_{name}", 0.0, "unavailable"))
            continue
        rows.append(
            (
                f"ir_backend_{name}",
                entry["us_per_instance"],
                f"{cells} cells total={entry['ms']:.1f}ms "
                f"speedup={entry['speedup_vs_numpy']}x",
            )
        )
        rows.append(
            (
                f"ir_backend_{name}_compile",
                entry["compile_ms"] * 1e3,
                f"cold={entry['cold_ms']:.1f}ms warm={entry['ms']:.1f}ms",
            )
        )
    g = payload["fused_grid"]
    rows.append(
        (
            "fused_grid_per_step",
            g["per_step_ms"] * 1e3 / g["cells"],
            f"{g['cells']} cells total={g['per_step_ms']:.1f}ms",
        )
    )
    rows.append(
        (
            "fused_grid_batched",
            g["us_per_cell"],
            f"speedup={g['speedup_vs_per_step']}x "
            f"mismatches={g['decision_mismatches']}",
        )
    )
    rows.append(
        (
            "fused_grid_compile",
            (g["fused_cold_ms"] - g["fused_warm_ms"]) * 1e3,
            f"cold={g['fused_cold_ms']:.1f}ms "
            f"warm={g['fused_warm_ms']:.1f}ms",
        )
    )
    return rows


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--backend",
        choices=("numpy", "jax", "pallas"),
        default=None,
        help="IR timing backend for the 64-cell sweep "
        "(default: REPRO_IR_BACKEND env, else numpy)",
    )
    cli = parser.parse_args()
    from repro.obs import get_logger

    log = get_logger("ir_sweep")
    for name, us, note in run(backend=cli.backend) + backend_rows():
        log.data(f"{name},{us:.1f},{note}")
